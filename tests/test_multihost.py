"""Two-process jax.distributed smoke tests (the pod DCN init path).

Reference parity: ``distllm/parsl.py:172-252`` — the reference trusts
Parsl HTEX to stitch nodes together; here the equivalent trust boundary is
``jax.distributed.initialize`` joining per-host processes into one global
device view, exercised with two REAL processes on the CPU backend (Gloo
collectives) exactly the way the rendered PBS/Slurm pod scripts drive it:
topology via ``DISTLLM_JAX_*`` env vars, rank via the scheduler-rank
fallback (``parallel/multihost.py``).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _cpu_env(**extra: str) -> dict[str, str]:
    env = dict(os.environ)
    # Tests force the CPU (see tests/conftest.py).
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)
    env.update(extra)
    return env


_SPMD_DRIVER = textwrap.dedent(
    """
    import os, sys
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distllm_tpu.parallel.multihost import init_multihost, process_rank

    out_path = sys.argv[1]
    # Topology comes ONLY from the DISTLLM_JAX_* / scheduler-rank env,
    # like a rendered pod script.
    rank, size = init_multihost()
    assert (rank, size) == process_rank()
    assert size == 2, size

    devices = np.array(jax.devices())  # global view: one CPU per process
    assert devices.size == 2, devices
    mesh = Mesh(devices, ('data',))

    # Sharded forward: data-parallel batch, replicated weights — the same
    # layout the embed pipeline uses on a pod. Deterministic inputs so the
    # parent can recompute single-process.
    batch, dim, hidden = 4, 8, 16
    x = np.arange(batch * dim, dtype=np.float32).reshape(batch, dim) / 10
    w1 = np.sin(np.arange(dim * hidden, dtype=np.float32)).reshape(dim, hidden)
    w2 = np.cos(np.arange(hidden * dim, dtype=np.float32)).reshape(hidden, dim)

    from jax.experimental import multihost_utils

    local = x[rank * (batch // 2) : (rank + 1) * (batch // 2)]
    gx = multihost_utils.host_local_array_to_global_array(
        local, mesh, P('data')
    )

    @jax.jit
    def forward(x, w1, w2):
        return jax.nn.gelu(x @ w1) @ w2

    y = jax.jit(
        forward, out_shardings=NamedSharding(mesh, P())
    )(gx, w1, w2)  # replicated output -> every process holds the full batch
    np.save(out_path, np.asarray(y))
    """
)


def test_two_process_sharded_forward_matches_single(tmp_path):
    port = _free_port()
    procs = []
    for rank in range(2):
        out = tmp_path / f'rank{rank}.npy'
        env = _cpu_env(
            DISTLLM_JAX_COORDINATOR=f'127.0.0.1:{port}',
            DISTLLM_JAX_NUM_PROCESSES='2',
            # Rank arrives via the scheduler-rank fallback chain, the way
            # srun/mpiexec deliver it (SLURM_PROCID on Slurm pods).
            SLURM_PROCID=str(rank),
        )
        procs.append(
            (
                subprocess.Popen(
                    [sys.executable, '-c', _SPMD_DRIVER, str(out)],
                    env=env,
                    cwd=REPO,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                ),
                out,
            )
        )
    try:
        for proc, _ in procs:
            stdout, _ = proc.communicate(timeout=180)
            assert proc.returncode == 0, stdout[-2000:]
    finally:
        # A timeout/assert must not LEAK the other rank: an orphaned
        # Gloo-barrier process spins at 100% CPU forever and starves
        # every test after this one (measured: the tier-1 run burned its
        # whole remaining budget here on a 1-core box).
        for proc, _ in procs:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()

    # Single-process reference on this process's CPU backend.
    import jax

    x = np.arange(4 * 8, dtype=np.float32).reshape(4, 8) / 10
    w1 = np.sin(np.arange(8 * 16, dtype=np.float32)).reshape(8, 16)
    w2 = np.cos(np.arange(16 * 8, dtype=np.float32)).reshape(16, 8)
    expected = np.asarray(jax.nn.gelu(x @ w1) @ w2)

    for _, out in procs:
        got = np.load(out)
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


def test_pod_worker_joins_jax_runtime(tmp_path):
    """The worker CLI's --jax-distributed flag end-to-end: a real fabric
    coordinator plus one worker process that joins the (size-1) global JAX
    runtime before serving, then completes a task that reads the runtime."""
    from distllm_tpu.parallel.fabric import Coordinator, ZmqPoolExecutor

    coordinator = Coordinator(bind='tcp://*:0', advertise_host='127.0.0.1')
    jax_port = _free_port()
    env = _cpu_env(
        DISTLLM_JAX_COORDINATOR=f'127.0.0.1:{jax_port}',
        DISTLLM_JAX_NUM_PROCESSES='1',
        DISTLLM_JAX_PROCESS_ID='0',
        # The pickled task fn lives in this test module; workers resolve
        # it by import path, same as Parsl's module-level-fn rule.
        PYTHONPATH=str(REPO / 'tests'),
    )
    proc = subprocess.Popen(
        [
            sys.executable,
            '-m',
            'distllm_tpu.parallel.worker',
            '--coordinator',
            coordinator.endpoint,
            '--jax-distributed',
        ],
        env=env,
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        executor = ZmqPoolExecutor(coordinator)
        # map() blocks forever if the worker dies before joining (e.g. a
        # jax.distributed incompatibility) — bound it so a wedged worker
        # costs one failed test, not the whole remaining tier-1 budget
        # (measured on a 1-core box: this line ate every test after it).
        import threading

        result_box: dict = {}
        mapper = threading.Thread(
            target=lambda: result_box.update(
                r=executor.map(_report_runtime, [0])
            ),
            daemon=True,
        )
        mapper.start()
        mapper.join(timeout=150)
        assert 'r' in result_box, (
            'worker never completed the task (map wedged); worker log:\n'
            + (proc.stdout.read()[-2000:] if proc.poll() is not None else
               '<worker still running>')
        )
        results = result_box['r']
        assert results == [(0, 1)]
        # Graceful teardown MUST work without signals: a worker in the
        # global JAX runtime swallows SIGTERM (preemption notifier), so
        # the poison pill is the only clean exit on a pod.
        executor.shutdown()
        stdout, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            stdout, _ = proc.communicate(timeout=30)
    assert proc.returncode == 0, stdout[-2000:]
    assert 'jax runtime rank 0/1' in stdout, stdout[-2000:]


def _report_runtime(_item):
    from distllm_tpu.parallel.multihost import process_rank

    return process_rank()
