"""Analytic roofline accounting for the serving engine (ISSUE 10 tentpole).

Turns per-window wall times into *utilization* — the number that tells an
operator whether a slow serving loop is leaving silicon on the table or is
already at the hardware's edge. Two rooflines matter here, matching the
engine's measured regimes (docs/serving.md):

- **compute (MFU)** — achieved matmul FLOP/s over the chip's bf16 peak.
  Prefill lives on this roof: one weight pass amortized over the whole
  padded batch.
- **weight-stream bandwidth** — bytes of weights read from HBM per second
  over the chip's HBM peak. Decode lives on this roof: every scan step of
  a fused window re-reads the entire weight set to emit one token per row,
  so a decode window's byte cost is ``decode_steps x weight_bytes``
  regardless of batch — the exact reason the mixed/speculative windows
  exist (ride or skip weight passes).

The model is deliberately a *weight-stream* roofline: attention KV traffic
and activation bytes are omitted (at serving batches on this family they
are second-order next to 13.5 GiB of weights per pass, and omitting them
makes the bandwidth-utilization gauge a conservative lower bound). FLOPs
use the classic ``2 * n_params`` per scored token (matmuls only), with
``n_params`` the parameters a token is multiplied by: of a routed expert
bank (``[L, E_held, ...]`` beside a ``router``) the ``experts_per_token /
E_routed`` share a token reaches, not the whole bank.

Costs come from the engine's *actual* parameter tree — ``sum(leaf.size)``
and ``sum(leaf.nbytes)`` over ``jax.tree.leaves`` — so quantized codes,
migrated layouts, and MoE trees are all priced as the bytes that really
stream, with no per-architecture formula to drift. One rule reads the tree's
names: where an output head of its own (``head``) stands beside the
embedding (``embed``), the embedding is a lookup of a row a token and is
left out of both sides (``models/falcon_h1.py``: 1.34 B of 5.25 B held
parameters at the cut the benchmark runs).

Peaks come from a device-kind table (TPU generations), overridable with
``DISTLLM_PEAK_FLOPS`` / ``DISTLLM_PEAK_BW_BYTES`` for new silicon. On
platform 'cpu' (the test tier) order-of-magnitude placeholder peaks
keep the gauges populated — the *absolute* CPU numbers are meaningless,
but the per-kind ratios and the plumbing they exercise are exactly what
the smoke tests pin.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

# device_kind prefix -> (bf16 peak FLOP/s, HBM bandwidth bytes/s).
# Matched case-insensitively by prefix, longest prefix wins.
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    'TPU v4': (275e12, 1.2288e12),
    'TPU v5 lite': (197e12, 8.19e11),
    'TPU v5e': (197e12, 8.19e11),
    'TPU v5p': (459e12, 2.765e12),
    'TPU v5': (459e12, 2.765e12),
    'TPU v6 lite': (918e12, 1.64e12),
    'TPU v6e': (918e12, 1.64e12),
}

# Order-of-magnitude placeholder for platform 'cpu' ONLY (the test tier):
# a few-core server class machine. Utilization numbers computed from it
# exercise the plumbing, not the silicon, and are never device metrics.
CPU_PLACEHOLDER_PEAKS = (1e12, 1e11)


def device_peaks(device) -> tuple[float, float]:
    """``(peak_flops, peak_hbm_bytes_per_s)`` for a jax device.

    Env overrides ``DISTLLM_PEAK_FLOPS`` / ``DISTLLM_PEAK_BW_BYTES`` win
    over the table (new silicon, calibrated numbers). A device kind that
    is not in :data:`DEVICE_PEAKS` raises, except on platform ``cpu``,
    which gets :data:`CPU_PLACEHOLDER_PEAKS`.
    """
    kind = device.device_kind.lower()
    flops = bw = None
    best = -1
    for name, (f, b) in DEVICE_PEAKS.items():
        if kind.startswith(name.lower()) and len(name) > best:
            best, flops, bw = len(name), f, b
    env_flops = os.environ.get('DISTLLM_PEAK_FLOPS')
    env_bw = os.environ.get('DISTLLM_PEAK_BW_BYTES')
    if flops is None and device.platform == 'cpu':
        flops, bw = CPU_PLACEHOLDER_PEAKS
    if flops is None and not (env_flops and env_bw):
        raise ValueError(
            f'device kind {device.device_kind!r} (platform '
            f'{device.platform!r}) is not in DEVICE_PEAKS; add its '
            'published peaks, or set DISTLLM_PEAK_FLOPS and '
            'DISTLLM_PEAK_BW_BYTES'
        )
    if env_flops:
        flops = float(env_flops)
    if env_bw:
        bw = float(env_bw)
    return flops, bw


@dataclass(frozen=True)
class StepCost:
    """Analytic cost of one engine step: matmul FLOPs + HBM weight bytes."""

    flops: float
    hbm_bytes: float


def _unreached_expert_params(tree, experts_per_token: int) -> float:
    """Parameters of routed expert banks that a token is NOT multiplied
    by: every dict with a ``router`` holds banks ``gate``/``up``/``down``
    shaped ``[..., E_held, in, out]``, of which a token reaches
    ``experts_per_token / E_routed`` (``E_routed`` the router's last dim)."""
    import jax

    if not isinstance(tree, dict):
        return 0.0
    unreached = 0.0
    if 'router' in tree:
        routed = jax.tree.leaves(tree['router'])[0].shape[-1]
        share = min(1.0, experts_per_token / routed)
        for name in ('gate', 'up', 'down'):
            for leaf in jax.tree.leaves(tree.get(name, ())):
                unreached += getattr(leaf, 'size', 0) * (1.0 - share)
    return unreached + sum(
        _unreached_expert_params(child, experts_per_token)
        for child in tree.values()
    )


class CostModel:
    """Per-window-kind FLOPs/bytes model for one engine's weight set.

    Built once per engine from the live parameter tree; ``step_cost``
    prices each flight-recorded step kind from the fields the engine
    already records (tokens, batch, draft counts). The engine divides by
    the window's wall time and the device peaks to publish
    ``distllm_engine_mfu{kind}`` and
    ``distllm_engine_bandwidth_utilization{kind}``.
    """

    def __init__(
        self,
        n_params: float,
        weight_bytes: float,
        decode_steps: int,
        peak_flops: float,
        peak_hbm_bytes: float,
    ) -> None:
        if n_params <= 0 or weight_bytes <= 0:
            raise ValueError('cost model needs a non-empty parameter tree')
        self.n_params = float(n_params)
        self.weight_bytes = float(weight_bytes)
        self.decode_steps = max(1, int(decode_steps))
        self.peak_flops = float(peak_flops)
        self.peak_hbm_bytes = float(peak_hbm_bytes)

    @classmethod
    def from_params(
        cls, params, decode_steps: int, device=None, num_devices: int = 1,
        experts_per_token: int | None = None, layer_passes: int = 1,
    ) -> 'CostModel':
        """Price the ACTUAL weight set: quantized codes, scales, migrated
        layouts — whatever is in the tree is what streams from HBM.

        ``num_devices`` is the number of chips the params are sharded
        over (the engine passes the TP mesh size): leaf ``size``/
        ``nbytes`` report GLOBAL extents, so the aggregate peaks must
        scale with the mesh or every healthy multi-chip deployment would
        read ``num_devices``x too high.

        ``experts_per_token`` (a model with routed experts): a token is
        multiplied by that many of the router's experts, so the banks
        beside a ``router`` count at that share in ``n_params`` (the FLOPs
        side). The bytes side keeps every bank: a decode batch reaches them
        all.

        ``layer_passes`` (a looped model, ``CacheSpec.passes``): a token
        runs the stacked tree ``params['layers']`` that many times, so a
        step streams and multiplies by its weights that often; everything
        beside the stack counts once.
        """
        import jax

        leaves = jax.tree.leaves(params)
        n_params = sum(getattr(x, 'size', 0) for x in leaves)
        weight_bytes = sum(getattr(x, 'nbytes', 0) for x in leaves)
        if layer_passes > 1:
            for leaf in jax.tree.leaves(params['layers']):
                n_params += getattr(leaf, 'size', 0) * (layer_passes - 1)
                weight_bytes += getattr(leaf, 'nbytes', 0) * (layer_passes - 1)
        if experts_per_token:
            n_params -= _unreached_expert_params(params, experts_per_token)
        if isinstance(params, dict) and 'head' in params and 'embed' in params:
            # An output head of its own beside the embedding: the head is
            # read whole a step, the embedding a ROW a token (a lookup, no
            # matmul), so it is on neither side.
            for leaf in jax.tree.leaves(params['embed']):
                n_params -= getattr(leaf, 'size', 0)
                weight_bytes -= getattr(leaf, 'nbytes', 0)
        if device is None:
            device = jax.devices()[0]
        peak_flops, peak_bw = device_peaks(device)
        scale = max(1, int(num_devices))
        return cls(n_params, weight_bytes, decode_steps,
                   peak_flops * scale, peak_bw * scale)

    def step_cost(
        self,
        kind: str,
        *,
        tokens: int = 0,
        batch: int = 0,
        draft_tokens: int = 0,
        prefill_tokens: int = 0,
        weight_passes: int | None = None,
        positions: int | None = None,
    ) -> StepCost | None:
        """Cost of one recorded step, or ``None`` for kinds with no
        dispatch behind them (``request``/``preempt``/``event``).

        - ``prefill``: one weight pass scoring ``tokens`` positions.
        - ``decode``/``mixed``: ``decode_steps`` weight passes (the fused
          scan re-reads the weights every step, frozen slots included);
          FLOPs cover generated tokens plus any ridden chunk positions.
        - ``weight_passes`` and ``positions`` given (a ``decode`` window of
          a model that decides blocks of positions): that many weight passes
          and that many positions computed, whatever the tokens emitted.
        - ``spec``: ONE weight pass scoring every row's span —
          ``batch + draft_tokens`` positions (plus ridden chunks) — the
          whole speculative trade made visible: decode-scan bytes down by
          ``decode_steps``x, FLOPs up by the span width.
        """
        two_np = 2.0 * self.n_params
        if weight_passes is not None:
            # A window whose forwards outnumber or undercut its tokens (a
            # model that decides blocks of positions together): a forward
            # reads the weights once and computes its rows' positions.
            return StepCost(
                two_np * (positions or 0), self.weight_bytes * weight_passes
            )
        if kind == 'prefill':
            return StepCost(two_np * tokens, self.weight_bytes)
        if kind in ('decode', 'mixed'):
            return StepCost(
                two_np * (tokens + prefill_tokens),
                self.weight_bytes * self.decode_steps,
            )
        if kind == 'spec':
            positions = batch + draft_tokens + prefill_tokens
            return StepCost(two_np * positions, self.weight_bytes)
        return None

    def utilization(
        self, cost: StepCost, duration_s: float
    ) -> tuple[float, float]:
        """``(mfu, bandwidth_utilization)`` for a step that took
        ``duration_s`` — uncapped ratios (a >1.0 reading means the model
        or the peak table is wrong for this chip; clamping would hide
        that)."""
        if duration_s <= 0:
            return 0.0, 0.0
        return (
            cost.flops / duration_s / self.peak_flops,
            cost.hbm_bytes / duration_s / self.peak_hbm_bytes,
        )
