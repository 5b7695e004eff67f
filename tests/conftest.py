"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding is exercised on CPU via
``--xla_force_host_platform_device_count=8`` (the reference has no multi-node
tests at all — SURVEY.md section 4; we do better by running every collective
path on a virtual mesh in CI).

Tests force the CPU: the platform is pinned through the config API
before any backend is selected, whatever ``JAX_PLATFORMS`` says.
"""

import os

os.environ.setdefault('TOKENIZERS_PARALLELISM', 'false')
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8'
    ).strip()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope='session', autouse=True)
def _assert_cpu():
    devices = jax.devices()
    assert devices[0].platform == 'cpu', devices
    assert len(devices) == 8, devices
    yield


@pytest.fixture(scope='session')
def rng():
    return np.random.default_rng(0)
