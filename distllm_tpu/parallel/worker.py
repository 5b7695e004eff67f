"""Per-host fabric worker entry point.

Launched once per TPU host by the cluster scheduler (PBS/Slurm script or ssh
loop), analogous to Parsl's ``process_worker_pool`` that the reference's
MpiExecLauncher starts per node (``distllm/parsl.py:227-230``)::

    python -m distllm_tpu.parallel.worker --coordinator tcp://login-node:5555

``--jax-distributed`` additionally joins the host's JAX process to the
global runtime (``parallel/multihost.py``) before serving tasks, so a task
fn can build a mesh spanning every pod host. Topology comes from the
``DISTLLM_JAX_*`` environment the rendered job script exports (or JAX's
own pod auto-detection).
"""

from __future__ import annotations

import argparse

from distllm_tpu.observability.instruments import log_event


def main(argv: list[str] | None = None) -> int:
    from distllm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description='distllm-tpu fabric worker')
    parser.add_argument('--coordinator', required=True, help='tcp://host:port')
    parser.add_argument('--heartbeat-interval', type=float, default=5.0)
    parser.add_argument(
        '--idle-timeout',
        type=float,
        default=900.0,
        help='Exit after this many seconds without coordinator contact '
        '(self-destruct for stragglers that outlive the driver).',
    )
    parser.add_argument(
        '--jax-distributed',
        action='store_true',
        help='Join the global JAX runtime (multi-host mesh) before serving.',
    )
    args = parser.parse_args(argv)

    if args.jax_distributed:
        from distllm_tpu.parallel.multihost import init_multihost

        rank, size = init_multihost()
        log_event(f'[worker] jax runtime rank {rank}/{size}', component='worker')

    from distllm_tpu.parallel.fabric import FabricWorker

    worker = FabricWorker(
        args.coordinator,
        heartbeat_interval=args.heartbeat_interval,
        idle_timeout=args.idle_timeout,
    )
    log_event(f'[worker] connected to {args.coordinator}', component='worker')
    worker.run()
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
