"""``tests/conftest.py`` itself, each case a pytest run of its own in a
subprocess over a scratch directory whose ``conftest.py`` is this one: the
limit a test has, and the life of the run's compile cache."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

CONFTEST = Path(__file__).with_name('conftest.py')


def _run(tmp_path, body, limit=None, **env):
    (tmp_path / 'conftest.py').write_text(
        CONFTEST.read_text()
        + (f'\nTEST_LIMIT_S = {limit}\n' if limit is not None else '')
    )
    (tmp_path / 'test_it.py').write_text(body)
    inherited = {
        k: v for k, v in os.environ.items()
        if k not in ('JAX_COMPILATION_CACHE_DIR', 'PYTEST_XDIST_WORKER',
                     'PYTEST_XDIST_TESTRUNUID')
    }
    return subprocess.run(
        [sys.executable, '-m', 'pytest', 'test_it.py', '-q', '--rootdir', '.',
         '-p', 'no:cacheprovider', '-p', 'no:xdist', '-p', 'no:randomly'],
        cwd=tmp_path, env={**inherited, **env}, capture_output=True, text=True,
        timeout=240,
    )


def test_a_test_past_its_limit_fails_with_every_threads_stack(tmp_path):
    done = _run(tmp_path, (
        'import time\n'
        'def test_stuck():\n'
        '    time.sleep(30)\n'
        'def test_next_one_runs():\n'
        '    pass\n'
    ), limit=0.5)
    assert done.returncode == 1, done.stdout + done.stderr
    assert '1 failed, 1 passed' in done.stdout
    assert 'test_it.py::test_stuck ran over 0.5 s; every thread:' in done.stdout
    assert 'most recent call first' in done.stdout  # faulthandler's heading
    assert 'in test_stuck' in done.stdout


COMPILES = (
    'import os\n'
    'import jax\n'
    'def test_compiles():\n'
    '    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(7.0))\n'
    "    held = os.environ['JAX_COMPILATION_CACHE_DIR']\n"
    '    assert jax.config.jax_compilation_cache_dir == held\n'
    '    assert os.listdir(held)\n'
    "    open('held', 'w').write(held)\n"
)


@pytest.mark.parametrize('callers', [False, True], ids=['its_own', 'callers'])
def test_a_run_removes_the_cache_it_made_and_no_other(tmp_path, callers):
    given = tmp_path / 'given'
    env = {'JAX_COMPILATION_CACHE_DIR': str(given)} if callers else {}
    done = _run(tmp_path, COMPILES, **env)
    assert done.returncode == 0, done.stdout + done.stderr
    held = Path((tmp_path / 'held').read_text())
    if callers:
        assert held == given and any(given.iterdir())
    else:
        assert held.name.startswith('distllm-tests-jax-cache-pid')
        assert not held.exists()
