"""Readers for a decoder that holds conv state beside K/V pages of a few
attention layers (``lfm2_moe``): the decode step against its byte account,
and the paged kernel's decode calls against the larger of its two
rooflines. The kernel's calls are found by the PROGRAM they run in and the
SCOPE they run under (``kernel_seconds``), not by a result type,
which holds one ``max_num_seqs``. Each reader returns None where the run has
nothing to read: no traced slice, no program of that name (the parent of the
PR that added it), or no decode record with ``kv_blocks``."""

from __future__ import annotations

import glob

from benchmarks import lfm2_bytes, peaks
from benchmarks import trace as trace_mod
from benchmarks.readers.hybrid import _scope_of
from benchmarks.readers.tracing import module_step_ms
from benchmarks.trace import seconds_matching


def kernel_seconds(xspace) -> dict | None:
    """Device seconds of kernel calls (``custom-call`` ops: a Pallas
    kernel, the grouped matmul) by ``'<program> <scope>'`` from a parsed
    ``XSpace``: each call of the ``XLA Ops`` line is given to the program
    of the ``XLA Modules`` line that it starts in (``jit_lfm2_window_fn(
    <fingerprint>)``) and to the innermost ``distllm.<name>`` scope its
    metadata names, averaged over the device planes. None without such
    lines."""
    seconds: dict[str, float] = {}
    planes = 0
    for plane in xspace.planes:
        if not trace_mod.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = lines.get(trace_mod.OPS_LINE)
        modules = lines.get(trace_mod.MODULES_LINE)
        if ops is None or modules is None:
            continue
        planes += 1
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        runs = sorted(
            (e.offset_ps, e.offset_ps + e.duration_ps,
             plane.event_metadata[e.metadata_id].name)
            for e in modules.events
        )
        calls = {
            k: _scope_of(md, stat_names)
            for k, md in plane.event_metadata.items()
            if 'custom-call' in md.name
        }
        at = 0
        for event in sorted(ops.events, key=lambda e: e.offset_ps):
            if event.metadata_id not in calls:
                continue
            while at < len(runs) and runs[at][1] <= event.offset_ps:
                at += 1
            if at == len(runs) or runs[at][0] > event.offset_ps:
                continue  # outside every program's run
            key = f'{runs[at][2]} {calls[event.metadata_id]}'.strip()
            seconds[key] = seconds.get(key, 0.0) + event.duration_ps / 1e12
    return {k: v / planes for k, v in seconds.items()} if planes else None


def load_xspace(capture):
    """The traced slice's ``XSpace``, parsed once from the profiler's own
    file: the driver calls this after the window, before the harness loads
    the trace and removes the files, and hands it to ``kernel_seconds`` and
    to ``hybrid.scope_seconds`` (a call's trace is hundreds of megabytes:
    each parse is seconds of a traced run). None, never an error, without a
    finished capture, without the protobuf's Python module, or without a
    file."""
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
        return xspace
    except Exception:  # a metric left out, never a failed run
        return None


def _windows(obs, capture=None) -> list[dict]:
    """The window's decode records that carry their rows' block count; with
    ``capture``, those that start inside the traced slice."""
    records = [
        r for r in obs['flight']
        if r.get('kind') == 'decode' and 'kv_blocks' in r and 'batch' in r
    ]
    if capture is None:
        return records
    if capture.t_start is None or capture.t_stop is None:
        return []
    return [
        r for r in records
        if 't0_s' in r and capture.t_start <= r['t0_s'] <= capture.t_stop
    ]


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (``lfm2_bytes.decode_step_bytes``:
    held weights once, the K and V pages of the rows' contexts once, from
    the decode records' block counts, so rounded up to whole blocks, and
    twice the conv state of the rows that ran) over the HBM peak, over the
    device time of one step of the decode program: the share of the whole
    step."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    windows = _windows(obs)
    if step_ms is None or not windows:
        return None
    rows = sum(r['batch'] for r in windows) / len(windows)
    tokens = ctx.config['engine']['block_size'] * sum(
        r['kv_blocks'] for r in windows
    ) / len(windows)
    bytes_moved = lfm2_bytes.decode_step_bytes(ctx.config, rows, tokens)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)


def paged_attn_roofline_share(ctx, obs, pattern: str):
    """For the decode windows of the traced slice: the seconds the paged
    kernel cannot do without, ``max(bytes / HBM peak, operations / bf16
    peak)`` of what the records' ``kv_blocks`` ask of it (a page's bytes
    once for K and once for V in each attention layer, the operations of
    every query head; times ``decode_steps``), over the device seconds of
    the kernel's calls in the programs and under the scope that ``pattern``
    finds among ``obs['kernel_call_s']``'s ``'<program> <scope>'`` keys."""
    calls = obs.get('kernel_call_s')
    if not calls:
        return None
    kernel_s = seconds_matching(calls, pattern)
    windows = _windows(obs, ctx.capture)
    if not kernel_s or not windows:
        return None
    engine = ctx.config['engine']
    tokens = engine['decode_steps'] * engine['block_size'] * sum(
        r['kv_blocks'] for r in windows
    )
    peak_flops, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    least_s = max(
        lfm2_bytes.kv_bytes(ctx.config, tokens) / peak_bw,
        lfm2_bytes.attn_flops(ctx.config, tokens) / peak_flops,
    )
    return 100.0 * least_s / kernel_s
