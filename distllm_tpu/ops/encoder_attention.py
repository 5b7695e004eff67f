"""Pallas TPU flash-style attention for the *encoder* (embed) forward.

Why not XLA SDPA here: at the embed pipeline's hot shape ([512, 256],
12 heads) XLA materializes the masked ``[B, N, S, S]`` score/softmax
tensors in HBM — ~0.8 GB per intermediate per layer, several GB of HBM
traffic that capped the whole forward at ~0.43 MFU in a 2026-07-31
record on older code (not re-measured). Why not
``jax.experimental.pallas.ops.tpu.flash_attention``: its ``MIN_BLOCK_SIZE = 128`` forces sequence lengths to
multiples of 128, which conflicts with the fine bucket ladder (160/224/320
rungs) that keeps embed padding waste low (``models/tokenizer.py
bucket_ladder``).

This kernel instead:

- takes Q/K/V in the ``[B, S, N*Hd]`` layout the QKV projections already
  produce — no head transpose is ever materialized;
- grids over the batch only; one grid step holds a full ``[S, N*Hd]``
  Q/K/V slice in VMEM (<= 2.3 MB each at S=512, H=768) and loops the
  heads in-kernel, so K/V bytes move HBM->VMEM exactly once;
- keeps the whole ``[S, S]`` per-head score tile in VMEM registers
  (<= 1 MB fp32 at S=512) — scores never touch HBM;
- masks invalid keys from the ``[B, S]`` attention mask with a -1e9 bias
  (finite, so fully-padded rows softmax to uniform garbage instead of
  NaN; poolers mask those rows out downstream).

Supported: S a multiple of 32, head_dim a multiple of 8 (BERT/ESM's 64
included), encoder-style bidirectional attention with key-validity mask.
The serving path's decode kernel is separate (``ops/paged_attention.py``).

Reference parity note: the reference gets this op from flash-attn/SDPA
inside HF models (``distllm/embed/encoders/auto.py:119-138``, faesm for
ESM); this is the TPU-native equivalent (SURVEY.md section 2.4 N3).

Routing policy. The numbers are a record of 2026-07-31 on older code
(an encoder-by-shape matrix; PERF.md section 7), NOT re-measured on
today's code — hypotheses that explain the policy, not results (constant
token budget B*S = 128k per forward):

- bert-base S=160..512: kernel 538-557k tok/s vs XLA 364-445k
  (+21-52%), and the kernel is FLAT across the bucket ladder where XLA
  degrades with S — exactly the shape regime the embed bench serves.
- esm2-650m S=256/512: kernel 78-81k vs XLA 47-62k (+27-72%).
- modernbert-base S=256/512 (windowed bias): kernel 357k vs XLA
  257-343k (+4-39%).
- S=1024 rows at 650m/modernbert dims exceed the VMEM working-set gate
  (shape_supported) and serve on XLA SDPA — 79k / 147k tok/s there.

So ``'auto'`` = kernel wherever :func:`shape_supported` passes, XLA
otherwise — the policy below implements exactly that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_BIG = -1e9

# Leave headroom under the ~16 MB/core VMEM for Mosaic's own buffers.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def shape_supported(
    seq_len: int, hidden: int, num_heads: int, itemsize: int = 2,
    has_bias: bool = False,
) -> bool:
    """True when this kernel can run the shape: S % 32 == 0, head_dim % 8
    == 0, and the per-grid-step working set (double-buffered Q/K/V/O blocks
    + the [S, S] fp32 score tile, doubled when an additive ``[S, S]`` bias
    rides along) fits in VMEM. Callers fall back to XLA SDPA otherwise
    (e.g. ESM2-3B's hidden=2560 at S=512). ``itemsize`` is the activation
    dtype's bytes (2 for bf16, 4 for fp32 parity runs)."""
    if seq_len % 32 or hidden % num_heads or (hidden // num_heads) % 8:
        return False
    blocks = 4 * seq_len * hidden * itemsize * 2  # q/k/v/o, double-buffered
    # Bias is an input operand too, so cost it double-buffered like the
    # blocks, on top of the in-kernel [S, S] fp32 score tile.
    scores = seq_len * seq_len * 4 * (3 if has_bias else 1)
    return blocks + scores <= _VMEM_BUDGET_BYTES


def resolve_use_pallas(
    attn_impl: str,
    seq_len: int,
    hidden: int,
    num_heads: int,
    dtype,
    has_bias: bool = False,
) -> bool:
    """Shared encoder-model policy for ``attn_impl``: ``'pallas'`` forces
    the kernel, ``'auto'`` picks it on TPU when :func:`shape_supported`,
    anything else means XLA SDPA. One definition so BERT/ESM can't
    silently diverge in backend selection."""
    if attn_impl == 'pallas':
        return True
    if attn_impl != 'auto':
        return False
    return jax.default_backend() == 'tpu' and shape_supported(
        seq_len, hidden, num_heads, jnp.dtype(dtype).itemsize, has_bias
    )


def _kernel(q_ref, k_ref, v_ref, mask_ref, *rest, num_heads: int,
            scale: float, has_bias: bool):
    if has_bias:
        bias_ref, o_ref = rest
    else:
        (o_ref,) = rest
    seq, dim = q_ref.shape[1], q_ref.shape[2]
    head_dim = dim // num_heads
    # [S] key-validity bias, shared by every head of this batch row. (The
    # mask arrives as [B, 1, S] — Mosaic requires a block's last two dims
    # to divide (8, 128) or equal the array's, which a [1, S] block of a
    # [B, S] array does not.)
    bias = jnp.where(mask_ref[0, 0] != 0, 0.0, _NEG_BIG).astype(jnp.float32)
    if has_bias:
        # Additive [S, S] term (e.g. ModernBERT's sliding-window mask),
        # shared by every head and batch row; folded into the key bias.
        bias = bias[None, :] + bias_ref[...].astype(jnp.float32)
    else:
        bias = bias[None, :]
    for h in range(num_heads):
        lo = h * head_dim
        qh = q_ref[0, :, lo:lo + head_dim]
        kh = k_ref[0, :, lo:lo + head_dim]
        vh = v_ref[0, :, lo:lo + head_dim]
        scores = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        scores = scores * scale + bias
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        out = jax.lax.dot_general(
            p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, :, lo:lo + head_dim] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=('num_heads', 'scale', 'interpret')
)
def encoder_attention(
    q: jnp.ndarray,  # [B, S, N*Hd]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,  # [B, S] nonzero = valid key
    num_heads: int,
    scale: float | None = None,
    bias: jnp.ndarray | None = None,  # [S, S] additive fp32 score term
    interpret: bool = False,
) -> jnp.ndarray:
    """Bidirectional multi-head attention, heads packed in the last dim.

    ``bias``, when given, is an additive ``[S, S]`` score term shared by
    every batch row and head — ModernBERT's sliding-window mask
    (``models/modernbert.py``) or any relative-position bias.
    """
    b, s, d = q.shape
    if d % num_heads:
        raise ValueError(f'hidden {d} not divisible by {num_heads} heads')
    if scale is None:
        scale = (d // num_heads) ** -0.5
    has_bias = bias is not None
    kernel = functools.partial(_kernel, num_heads=num_heads,
                               scale=float(scale), has_bias=has_bias)
    in_specs = [
        pl.BlockSpec((1, s, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, s, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, s, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((1, 1, s), lambda i: (i, 0, 0)),
    ]
    operands = [q, k, v, mask.astype(jnp.int32).reshape(b, 1, s)]
    if has_bias:
        in_specs.append(pl.BlockSpec((s, s), lambda i: (0, 0)))
        operands.append(bias.astype(jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, s, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
        ),
        interpret=interpret,
    )(*operands)


def encoder_attention_reference(q, k, v, mask, num_heads, scale=None,
                                bias=None):
    """Pure-jnp oracle for tests (same layout/mask semantics)."""
    b, s, d = q.shape
    hd = d // num_heads
    if scale is None:
        scale = hd ** -0.5
    qh = q.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)
    kh = k.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)
    vh = v.reshape(b, s, num_heads, hd).transpose(0, 2, 1, 3)
    scores = jnp.einsum('bnqh,bnkh->bnqk', qh, kh).astype(jnp.float32) * scale
    score_bias = jnp.where(mask[:, None, None, :] != 0, 0.0, _NEG_BIG)
    if bias is not None:
        score_bias = score_bias + bias[None, None].astype(jnp.float32)
    p = jax.nn.softmax(scores + score_bias, axis=-1)
    out = jnp.einsum('bnqk,bnkh->bnqh', p.astype(vh.dtype), vh)
    return out.transpose(0, 2, 1, 3).reshape(b, s, d)
