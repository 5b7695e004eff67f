"""Measured executable cost from XLA's own analysis (ISSUE 11 tentpole).

The analytic roofline (``observability/roofline.py``) prices engine steps
from the parameter tree — ``2 x n_params`` FLOPs per scored position,
weight bytes per pass. That is a *model*, and ROADMAP item 1 (the Pallas
ragged-attention kernel) needs *measured* device cost truth before it can
claim a win over it: a kernel that cuts real HBM traffic moves
``cost_analysis()`` bytes, not the hand math. This module prices each
compiled serving executable via ``compiled.cost_analysis()`` and
publishes the measured twins of the analytic gauges:

- ``distllm_engine_mfu_measured{kind}`` /
  ``distllm_engine_bandwidth_utilization_measured{kind}`` — per-dispatch
  utilization from what XLA compiled, beside the analytic gauges;
- ``distllm_engine_roofline_flops_ratio{kind}`` /
  ``distllm_engine_roofline_bytes_ratio{kind}`` — measured / analytic
  per dispatch, so calibration drift is a visible number. FLOPs near 1.0 = calibrated; bytes > 1.0
  is expected (KV + activation traffic the weight-stream model omits),
  and a jump means the compiled graph carries traffic the model cannot
  see (layout churn, materialized slices — the r03 845 ms window).

Pricing happens once per executable at warmup (``LLMEngine.warmup``);
the per-dispatch gauges then cost two multiplies. AOT-compiled
executables (the TPU auto-layout decode window) are priced for free;
``jax.jit`` wrappers are priced by ``lower().compile()``, which the
engine only does when the compile is cheap or cached (non-TPU backends,
or a persistent compilation cache is configured) — never a second
multi-minute unrolled compile on a cold TPU.

Only the jax imports are lazy; the module itself is dependency-free.
"""

from __future__ import annotations

from dataclasses import dataclass

from distllm_tpu.observability import instruments as _metrics


@dataclass(frozen=True)
class XlaCost:
    """Per-invocation cost of one compiled executable, as XLA measured
    it: total FLOPs and total HBM bytes accessed (inputs + outputs +
    temporaries). ``source`` records how it was obtained (``aot`` = a
    pre-compiled executable, ``lowered`` = jit wrapper re-lowered)."""

    flops: float
    bytes_accessed: float
    source: str

    def to_dict(self) -> dict:
        return {
            'flops': self.flops,
            'bytes_accessed': self.bytes_accessed,
            'source': self.source,
        }


def executable_cost(compiled, source: str = 'aot') -> XlaCost | None:
    """Price a compiled executable; ``None`` when the backend reports no
    FLOPs (cost analysis unsupported)."""
    try:
        cost = compiled.cost_analysis() or {}
    except Exception:
        return None
    flops = cost.get('flops')
    if not isinstance(flops, (int, float)) or flops <= 0:
        return None
    bytes_accessed = cost.get('bytes accessed')
    if not isinstance(bytes_accessed, (int, float)) or bytes_accessed < 0:
        bytes_accessed = 0.0
    return XlaCost(float(flops), float(bytes_accessed), source)


def price_callable(fn, *args) -> XlaCost | None:
    """Price whatever will actually run: an AOT-compiled executable
    directly, or a ``jax.jit`` wrapper via ``lower(*args).compile()``
    (identical HLO to the wrapper's own compile, so a configured
    persistent compilation cache makes it a disk hit). Returns ``None``
    on any failure — pricing is telemetry, never load-bearing."""
    import jax

    if isinstance(fn, jax.stages.Compiled):
        return executable_cost(fn, source='aot')
    try:
        compiled = fn.lower(*args).compile()
    except Exception:
        return None
    return executable_cost(compiled, source='lowered')


def publish_measured(
    kind: str,
    cost: XlaCost,
    duration_s: float,
    peak_flops: float,
    peak_hbm_bytes: float,
) -> tuple[float, float]:
    """Set the measured utilization gauges for one dispatch; returns
    ``(mfu, bw_util)`` (uncapped, mirroring the analytic gauges: a >1.0
    reading indicts the peak table, and clamping would hide that)."""
    if duration_s <= 0 or peak_flops <= 0 or peak_hbm_bytes <= 0:
        return 0.0, 0.0
    mfu = cost.flops / duration_s / peak_flops
    bw_util = cost.bytes_accessed / duration_s / peak_hbm_bytes
    _metrics.ENGINE_MFU_MEASURED.labels(kind=kind).set(mfu)
    _metrics.ENGINE_BW_UTIL_MEASURED.labels(kind=kind).set(bw_util)
    return mfu, bw_util


def record_calibration(
    kind: str, analytic_flops: float, analytic_bytes: float, cost: XlaCost
) -> tuple[float | None, float | None]:
    """Set the measured/analytic ratio gauges for one dispatch; returns
    ``(flops_ratio, bytes_ratio)`` (``None`` where the analytic side is
    zero — nothing to calibrate against)."""
    flops_ratio = bytes_ratio = None
    if analytic_flops > 0:
        flops_ratio = cost.flops / analytic_flops
        _metrics.ENGINE_ROOFLINE_FLOPS_RATIO.labels(kind=kind).set(
            flops_ratio
        )
    if analytic_bytes > 0 and cost.bytes_accessed > 0:
        bytes_ratio = cost.bytes_accessed / analytic_bytes
        _metrics.ENGINE_ROOFLINE_BYTES_RATIO.labels(kind=kind).set(
            bytes_ratio
        )
    return flops_ratio, bytes_ratio
