"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding is exercised on CPU via
``--xla_force_host_platform_device_count=8`` (the reference has no multi-node
tests at all — SURVEY.md section 4; we do better by running every collective
path on a virtual mesh in CI).

Tests force the CPU: the platform is pinned through the config API
before any backend is selected, whatever ``JAX_PLATFORMS`` says.

A run shares one persistent compile cache: every test builds its own
``LLMEngine``, whose programs are fresh closures over the same toy models, so
the same programs are compiled in every test, file and worker. The directory
is made empty by the process that starts the run (the xdist controller, whose
environment the workers and every subprocess a test starts inherit; a lone
process otherwise) and removed by it at the end, so a run depends on nothing
an earlier run left. A ``JAX_COMPILATION_CACHE_DIR`` the caller set is used
and is not removed.

Every test has ``TEST_LIMIT_S`` for its call; past it the test fails with
every thread's stack instead of costing the run its whole limit.
"""

import faulthandler
import os
import shutil
import signal
import tempfile

os.environ.setdefault('TOKENIZERS_PARALLELISM', 'false')
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8'
    ).strip()

# Read by jax itself (``utils.enable_compile_cache`` leaves a set variable
# alone). Under xdist a worker finds the controller's; were it started with
# another environment, the run's id names the same directory in every worker.
_RUN_CACHE = os.path.join(
    tempfile.gettempdir(),
    'distllm-tests-jax-cache-'
    + os.environ.get('PYTEST_XDIST_TESTRUNUID', f'pid{os.getpid()}'),
)
_OWNS_CACHE = (
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', _RUN_CACHE) == _RUN_CACHE
    and 'PYTEST_XDIST_WORKER' not in os.environ
)
if _OWNS_CACHE:  # what a killed run of the same pid left
    shutil.rmtree(_RUN_CACHE, ignore_errors=True)
os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS', '0')
os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES', '0')

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


def pytest_unconfigure(config):
    if _OWNS_CACHE:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


TEST_LIMIT_S = 300.0


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    def on_alarm(signum, frame):
        with tempfile.TemporaryFile('w+') as stacks:
            faulthandler.dump_traceback(stacks, all_threads=True)
            stacks.seek(0)
            pytest.fail(
                f'{item.nodeid} ran over {TEST_LIMIT_S} s; every thread:\n'
                + stacks.read(),
                pytrace=False,
            )

    before = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)
