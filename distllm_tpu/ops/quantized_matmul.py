"""Weight-only int8 matmul: dequantize in VMEM, not in HBM.

Why this exists: ``common.dense`` used to call
``QTensor.dequantize()`` and feed the bf16 result to the dot. Inside the
unrolled decode loop XLA materializes both the converted weight AND the
scale-multiplied copy in HBM — per layer, per step. The int8 serving run
that motivated this (a 2026-07-31 record on older code, in git history;
none of this module's numbers is re-measured) decoded 16-step
windows in 1242 ms at batch 128 against a ~200 ms weights+KV streaming
floor: the "quantized" model was streaming ~3x the bytes of the bf16 one.

The fix has two tiers, chosen by :func:`int8_dense`:

- **XLA scale-after-dot** — the tier ``'auto'`` always picks, because it
  won in that record: ``(x @ q.astype(dtype)) * scale`` is algebraically
  identical to ``x @ (q * scale)`` (the int8 scale is per-OUTPUT-channel;
  `quantization.quantize_int8` reduces only the input dim), the full-size
  elementwise multiply on the weight is gone, and XLA fuses the int8→bf16
  convert into the dot's weight stream. In the same record, at the 7B
  unrolled 16-step decode window: 315 ms at
  batch 32 = 1623 tok/s, vs 465 ms bf16 and 1242 ms for the old
  dequant-before-dot serving path.
- **Pallas kernel** (:func:`int8_matmul_pallas`): streams int8 tiles
  HBM->VMEM, converts in VMEM, applies the per-output-channel scale once
  to the fp32 accumulator at the last K step. Kept for explicit selection
  and as the substrate for future fused variants, but it lost to the XLA
  tier everywhere that record looked (same log: 720 ms/window at batch 32, 1676 ms
  at batch 128; 5.4x slower than bf16 on the 4096x32000 lm_head, where
  its 256-wide N tiles yield 2000 grid steps) — so 'auto' never picks it.

Reference parity note: the reference gets weight-only-quantized serving
from bitsandbytes via HF (`distllm/generate/generators/huggingface_backend.py:66-77`)
— CUDA kernels that likewise fuse dequant into the GEMM. SURVEY.md §2.4 N4.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BACKENDS = ('auto', 'pallas', 'xla', 'interpret')

_default_backend = os.environ.get('DISTLLM_QMM_BACKEND', 'auto')


def set_default_backend(backend: str) -> None:
    """Set the process-wide tier for :func:`int8_dense` callers that don't
    pass one (``models.common.dense``).

    Applies at TRACE time: executables already compiled keep the tier they
    were traced with (jax.jit caches by shape, not by this setting) — set
    it before the first compile, as the engine does for TP meshes.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f'unknown quantized-matmul backend {backend!r}; one of {BACKENDS}'
        )
    global _default_backend
    _default_backend = backend


def default_backend() -> str:
    return _default_backend


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, k_steps: int):
    """One (n, k) grid step: acc += x_tile @ dequant(q_tile).

    Grid is (n_steps, k_steps), k innermost: the x row-block stays
    resident while each output tile accumulates over K; q tiles stream
    exactly once. The scale lands on the [M, bn] accumulator — never on
    the weight.
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        q_ref[...].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(1) == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _pick_tile(dim: int, candidates=(512, 256, 128)) -> int | None:
    for c in candidates:
        if dim % c == 0:
            return c
    return None


# M beyond this, the (n, k) grid's "one x row-block" layout stops making
# sense (the accumulator scratch grows linearly with M) and the regime is
# compute-bound prefill where the XLA path is fine.
MAX_PALLAS_ROWS = 512


def pallas_supported(m: int, k: int, n: int) -> bool:
    """Can :func:`int8_matmul_pallas` take this shape?"""
    return (
        m <= MAX_PALLAS_ROWS
        and _pick_tile(k) is not None
        and _pick_tile(n) is not None
    )


@functools.partial(jax.jit, static_argnames=('interpret',))
def int8_matmul_pallas(
    x: jnp.ndarray,  # [M, K] float
    q: jnp.ndarray,  # [K, N] int8
    scale: jnp.ndarray,  # [1, N] (or [N]) f32 per-output-channel
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """``(x @ q) * scale`` with q staying int8 until VMEM. Returns x.dtype.

    ``interpret=True`` runs the kernel in Pallas interpret mode so CPU
    tests exercise the real index maps.
    """
    m, k = x.shape
    k2, n = q.shape
    assert k == k2, (x.shape, q.shape)
    bk = _pick_tile(k)
    bn = _pick_tile(n)
    if bk is None or bn is None or m > MAX_PALLAS_ROWS:
        raise ValueError(
            f'shape (M={m}, K={k}, N={n}) outside the pallas tile contract'
        )
    # Row-pad to the bf16 sublane multiple; padded rows are zeros and their
    # outputs are sliced away.
    m_pad = max(16, -(-m // 16) * 16)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    scale = scale.reshape(1, n).astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, k_steps=k // bk),
        grid=(n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((m_pad, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((m_pad, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m_pad, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
        ),
        interpret=interpret,
    )(x, q, scale)
    return out[:m] if m_pad != m else out


def int8_matmul_xla(
    x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray
) -> jnp.ndarray:
    """Scale-after-dot formulation; portable tier of :func:`int8_dense`."""
    y = jax.lax.dot_general(
        x,
        q.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (y * scale.reshape(1, -1).astype(jnp.float32)).astype(x.dtype)


def int8_dense(
    x: jnp.ndarray,  # [..., K]
    q: jnp.ndarray,  # [K, N] int8
    scale: jnp.ndarray,  # [..., 1, N] f32
    backend: str = 'auto',
) -> jnp.ndarray:
    """``x @ dequant(q, scale)`` for a 2-D int8 QTensor, any leading dims.

    ``backend``: 'auto' == 'xla' (scale-after-dot — the fastest tier in
    the old record, module docstring), 'pallas' / 'interpret' force the Pallas kernel
    (compiled / interpret mode).
    """
    if backend not in BACKENDS:
        raise ValueError(
            f'unknown quantized-matmul backend {backend!r}; one of {BACKENDS}'
        )
    k, n = q.shape
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, k)
    use_pallas = backend in ('pallas', 'interpret')
    if use_pallas:
        out = int8_matmul_pallas(
            x2, q, scale, interpret=(backend == 'interpret')
        )
    else:
        out = int8_matmul_xla(x2, q, scale)
    return out.reshape(*lead, n)
