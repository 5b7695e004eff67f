"""Readers for a hybrid (recurrent + attention, routed experts) decoder:
the decode step against its byte account, the share of routed pairs held
here, and the prefix cache's hit share. Each returns None where the run has
nothing to read (no traced slice, or a program without the counter)."""

from __future__ import annotations

import glob
import re

from benchmarks import hybrid_bytes, peaks
from benchmarks import trace as trace_mod
from benchmarks.readers.tracing import module_step_ms

_SCOPE = re.compile(r'distllm\.[A-Za-z_]+')


def _scope_of(metadata, stat_names: dict) -> str:
    """The innermost ``distllm.<name>`` scope (``jax.named_scope``) of an
    op, from its event metadata's statistics, where the profiler keeps the
    op's source path (``tf_op``: ``jit(f)/while/body/distllm.moe/...``).
    The grouped matmul's kernel call loses that path in XLA (``tf_op``
    ``ragged-dot-none:``); the programs call it under ``distllm.moe`` only,
    so its name stands for the scope. ``''`` for an op under none."""
    for stat in metadata.stats:
        value = stat.str_value or stat_names.get(stat.ref_value, '')
        if 'distllm.' in value:
            return _SCOPE.findall(value)[-1]
    return 'distllm.moe' if 'ragged-dot' in metadata.name else ''


def scope_seconds(xspace) -> dict | None:
    """Device self-seconds by scope from a parsed ``XSpace``: the ``XLA
    Ops`` line of every ``/device:TPU:n`` plane, each instant counted once
    (``trace.self_times``: a ``while`` keeps only what no child covers),
    averaged over the planes. None without such a line."""
    seconds: dict[str, float] = {}
    planes = 0
    for plane in xspace.planes:
        if not trace_mod.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        scopes = {
            k: _scope_of(md, stat_names) for k, md in plane.event_metadata.items()
        }
        for line in plane.lines:
            if line.name != trace_mod.OPS_LINE:
                continue
            planes += 1
            events = [
                [scopes.get(e.metadata_id, ''), e.offset_ps, e.duration_ps]
                for e in line.events
            ]
            for scope, ps in trace_mod.self_times(events).items():
                seconds[scope] = seconds.get(scope, 0.0) + ps / 1e12
    return {k: v / planes for k, v in seconds.items()} if planes else None


def collect_scope_seconds(capture) -> dict | None:
    """``scope_seconds`` of the traced slice, read from the profiler's own
    file: the driver calls this after the window, before the harness loads
    the trace and removes the files (the harness's structure keeps an op's
    name; the scope is in the op's metadata, which only the protobuf
    shows). None, never an error, without a finished capture, without the
    protobuf's Python module, or without a device plane."""
    if capture is None or capture.dir is None or not capture.done:
        return None
    found = glob.glob(f'{capture.dir}/plugins/profile/*/*.xplane.pb')
    if not found:
        return None
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

        xspace = xplane_pb2.XSpace()
        with open(found[0], 'rb') as fh:
            xspace.ParseFromString(fh.read())
        return scope_seconds(xspace)
    except Exception:  # a metric left out, never a failed run
        return None


def scope_time_share(ctx, obs, pattern: str):
    """Share of the device's busy time in ops under the scopes the pattern
    finds (self time, as ``tracing:op_time_share``)."""
    scopes, summary = obs.get('scope_s'), obs.get('trace')
    if not scopes or not summary or not summary['busy_s']:
        return None
    return 100.0 * trace_mod.seconds_matching(scopes, pattern) / summary['busy_s']


def _windows(obs) -> list[dict]:
    return [r for r in obs['flight'] if r.get('kind') == 'decode' and 'batch' in r]


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (``hybrid_bytes.decode_step_bytes``:
    held weights once, twice the state of the rows that ran, the K and V of
    their contexts) over the HBM peak, over the device time of one step of
    the decode program."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    windows = _windows(obs)
    per_sequence = obs['counters'].get('mean_context_tokens')
    if step_ms is None or not windows or per_sequence is None:
        return None
    rows = sum(r['batch'] for r in windows) / len(windows)
    bytes_moved = hybrid_bytes.decode_step_bytes(
        ctx.config, rows, per_sequence * rows
    )
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def moe_held_pair_share(ctx, obs):
    """``moe_pairs_held / moe_pairs`` over the window's decode records: of
    the (token, expert) pairs the router made, the share whose expert is
    held on this chip."""
    pairs = sum(r.get('moe_pairs', 0) for r in _windows(obs))
    if not pairs:
        return None
    held = sum(r.get('moe_pairs_held', 0) for r in _windows(obs))
    return 100.0 * held / pairs


def prefix_hit_share(ctx, obs):
    """Cached prompt tokens over prompt tokens, from the window's
    ``request`` records."""
    requests = [
        r for r in obs['flight']
        if r.get('kind') == 'request' and r.get('prompt_tokens')
    ]
    prompt = sum(r['prompt_tokens'] for r in requests)
    if not prompt:
        return None
    return 100.0 * sum(r.get('cached_tokens', 0) for r in requests) / prompt
