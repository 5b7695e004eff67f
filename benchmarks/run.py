"""Run one cell of the benchmark once and print the result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's file (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``), a driver (``drivers/<driver>.py``) and the
traffic parameters; the manifest (``BENCHMARK.json``) says which metrics the
cell reports. ``--trace 0`` prints the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics, the device's busy seconds and a breakdown. A run
that finds no TPU fails; ``--allow-cpu`` is the rehearsal switch of the tiny
CPU tests and prints no metric under its name (``rehearsal_metrics``).
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from benchmarks import trace as trace_mod
from benchmarks.manifest import Manifest


@dataclass
class Context:
    """What a driver and a reader get to see of one run."""

    cell: str
    workload: dict  # workloads/<cell>.json
    config: dict  # configs/<config>.json
    seed: int
    seconds: float
    devices: list
    capture: trace_mod.Capture
    rehearsal: bool
    _compiles: list = field(default_factory=list)

    @property
    def traffic(self) -> dict:
        return self.workload['traffic']

    @property
    def device_kind(self) -> str:
        return self.devices[0].device_kind

    def compiles(self) -> int:
        """Programs compiled or loaded from the compile cache so far."""
        return len(self._compiles)

    def compiled_since(self, count: int) -> list[list]:
        """``[program, seconds]`` of each one since ``compiles()`` read
        ``count``."""
        return [list(entry) for entry in self._compiles[count:]]


def _watch_compiles(sink: list) -> None:
    import jax.monitoring

    def on_duration(event: str, seconds: float, **kw) -> None:
        # Fires once per program that is compiled, or looked up in the
        # persistent cache, on its first use in the process.
        if event == '/jax/core/compile/backend_compile_duration':
            sink.append((str(kw.get('fun_name', '?')), seconds))

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _watch_gc(sink: list) -> None:
    """``[generation, seconds]`` of every collection of Python's garbage
    collector from now on: a full one over the heap that set-up leaves takes
    seconds, and one that falls inside a window stalls the host for them."""
    import gc

    started = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == 'start':
            started[0] = time.perf_counter()
        else:
            sink.append([info['generation'], time.perf_counter() - started[0]])

    gc.callbacks.append(on_gc)


def _parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), required=True)
    parser.add_argument('--manifest', default=str(_ROOT / 'BENCHMARK.json'))
    parser.add_argument('--allow-cpu', action='store_true')
    return parser.parse_args(argv)


def _peak_bytes(devices) -> int | None:
    peaks = []
    for device in devices:
        stats = device.memory_stats() or {}
        if 'peak_bytes_in_use' in stats:
            peaks.append(int(stats['peak_bytes_in_use']))
    return max(peaks) if peaks else None


def _read_per_layer(manifest, names, ctx, obs) -> dict:
    values = {}
    for entry in names:
        spec = manifest.load('metrics', entry['name'])
        module_name, func_name = spec['reader'].split(':')
        module = importlib.import_module(f'benchmarks.readers.{module_name}')
        value = getattr(module, func_name)(ctx, obs, **spec.get('args', {}))
        if value is not None:  # a reader that found nothing reports nothing
            values[entry['name']] = {'value': value, 'unit': entry['unit']}
    return values


def main(argv=None) -> int:
    args = _parse(argv)
    manifest = Manifest(Path(args.manifest))
    cell = manifest.cell(args.workload)
    workload = manifest.load('workloads', cell['name'])
    config = manifest.load('configs', cell['config'])
    if workload['config'] != cell['config']:
        raise SystemExit(
            f"{cell['name']}: the cell's file names configuration "
            f"{workload['config']!r}, the manifest {cell['config']!r}"
        )

    if not (_ROOT / 'distllm_tpu').is_dir():
        # The system under test is the checkout's own, never an installed one.
        print(f'no program to measure under {_ROOT}', file=sys.stderr)
        return 2

    import jax

    from distllm_tpu.utils import enable_compile_cache

    # The program's own placement: JAX_COMPILATION_CACHE_DIR where it is set,
    # else the fixed <checkout>/.jax_cache.
    enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != 'tpu' and not args.allow_cpu:
        print(f'no TPU: jax found platform {platform!r}', file=sys.stderr)
        return 2
    if len(devices) < cell['chips']:
        print(
            f"{cell['name']} needs {cell['chips']} chips, jax found "
            f'{len(devices)}', file=sys.stderr,
        )
        return 2

    trace_spec = workload.get('trace', {})
    capture = trace_mod.Capture(
        delay_s=float(trace_spec.get('delay_s', 2.0)),
        length_s=(
            min(float(trace_spec.get('seconds', 10.0)), args.seconds)
            if args.trace else 0.0
        ),
    )
    ctx = Context(
        cell=cell['name'], workload=workload, config=config, seed=args.seed,
        seconds=args.seconds, devices=devices[: cell['chips']],
        capture=capture, rehearsal=platform != 'tpu',
    )
    _watch_compiles(ctx._compiles)
    driver = importlib.import_module(f"benchmarks.drivers.{workload['driver']}")

    state = driver.prepare(ctx)
    setup_programs = ctx.compiles()
    # Set-up: process start to the first measured operation, less the seconds
    # the driver spent on inputs to the correctness check.
    setup_s = time.perf_counter() - _T_PROCESS - state.get('excluded_s', 0.0)
    collections: list = []
    _watch_gc(collections)
    obs = driver.measure(state, ctx)
    gc_in_window = [c for c in collections if c[1] >= 0.05]
    compiled_in_window = ctx.compiled_since(setup_programs)
    obs['compiles_in_window'] = len(compiled_in_window)
    traced = capture.load()
    memory_peak = _peak_bytes(ctx.devices)
    obs['trace'] = trace_mod.summarize(traced) if traced else None
    correct, detail = driver.verify(state, ctx, obs)

    obs['end_to_end']['setup_s'] = setup_s
    device = {
        'platform': platform,
        'kind': ctx.device_kind,
        'count': len(devices),
        'memory_peak_bytes': memory_peak,
    }
    result = {
        'correct': bool(correct),
        'attempted': int(obs['attempted']),
        'failed': int(obs['failed']),
    }
    if args.trace:
        metrics = _read_per_layer(
            manifest, manifest.metrics_of('per_layer', ctx.cell), ctx, obs
        )
        summary = obs['trace']
        if summary is not None:
            device['busy_s'] = summary['busy_s']
            device['window_s'] = capture.t_stop - capture.t_start
            result['breakdown'] = {
                'device_ops': trace_mod.top(summary['op_s']),
                'idle_gaps': trace_mod.top(summary['gap_s']),
            }
    else:
        metrics = {
            m['name']: {'value': obs['end_to_end'][m['name']], 'unit': m['unit']}
            for m in manifest.metrics_of('end_to_end', ctx.cell)
        }
    # A number from a CPU run is never written under a device metric's name.
    result['rehearsal_metrics' if ctx.rehearsal else 'metrics'] = metrics
    if ctx.rehearsal:
        result['metrics'] = {}
    result['device'] = device
    result['detail'] = {
        **detail, **obs.get('detail', {}), 'window_s': obs['window_s'],
        'setup_programs': setup_programs,
        'compiles_in_window': obs['compiles_in_window'],
        'compiled_in_window': compiled_in_window[:40],
        'gc_in_window_s': gc_in_window[:40],
    }
    driver.close(state)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
