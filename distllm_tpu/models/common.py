"""What the pure-JAX models share.

Everything is a pure function over explicit parameter pytrees; per-layer
weights are stacked on a leading axis.

- The encoders' and decoders' layers: norms, ``dense`` (quantized kernels
  dequantize at the point of use), activations, ``sdpa``, RoPE tables and
  rotation, GQA's ``repeat_kv``, masks.
- What a served decoder declares: ``PagedGroup`` and ``CacheSpec``.
- What a served decoder does NOT write itself (docs/serving.md, "What a
  family writes"): ``decode_window``, the decode window's step scan over a
  family's ``_decode_core``; ``once_a_kind``, ``layer_at``, ``swiglu``,
  ``dense_mlp``, ``finish_layer`` and ``last_token`` for a walk over
  stacked layers unrolled; ``seeded_tree`` and ``tree_specs``, random
  weights made on the device from a family's table of leaf shapes.

Nothing here may test a family's name or ``model_type``: a helper lives
here only where the families' copies were equal after renaming.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class PagedGroup:
    """Layers of a decoder that share one paged K/V pool and one block
    table. ``window`` None: a query sees its whole context and a sequence
    holds blocks for all of it. ``window`` w: a query at position ``p`` sees
    keys ``p - w < j <= p``, and the sequence holds only the blocks such a
    query can still see (``generate/engine/kv_cache.WindowBlocks``).

    ``row`` makes the group LATENT (``models/deepseek_v3.py``): a layer
    holds ONE plane whose token rows are ``row`` values wide, and a token's
    value is the first ``value_lanes`` lanes of its row, so there is no V
    pool. ``row`` None, the default, is a K pool and a V pool whose rows
    are ``num_kv_heads * head_dim`` wide."""

    name: str
    num_layers: int
    window: int | None = None
    row: int | None = None
    value_lanes: int | None = None

    @property
    def stored_row(self) -> int | None:
        """``row`` in whole 128-lane tiles, as the pool stores it: the
        TPU's tiled layout holds a 576-wide minor dim in 640 lanes whatever
        the shape says, and the kernel copies pages by whole tiles."""
        return None if self.row is None else -(-self.row // 128) * 128


@dataclass(frozen=True)
class CacheSpec:
    """What one sequence of a decoder holds while it is served, as its
    config declares it (``cache_spec()``); the serving engine builds its
    pools, block tables, programs and refusals from this and from nothing
    else about the family.

    ``paged``: the paged groups, the full-context one first (its blocks
    are the scheduler's). ``state``: a pytree of ``ShapeDtypeStruct``, the
    fixed recurrent state of one sequence, or None. ``programs``: the
    module whose ``prefill_paged`` and ``decode_loop`` serve the family.
    With one group those take bare ``k_cache``, ``v_cache`` and
    ``block_tables``; with several, a tuple of each, one entry a group.
    ``program_prefix`` names the family's compiled programs
    (``jit_<prefix>window_fn``). ``dense_prefill``: whether the module's
    ``prefill`` (whole prompts, K/V scattered afterwards) may serve short
    fresh prompts; without it every prefill takes the paged route.
    A K/V group's ``k_cache`` and ``v_cache`` are each ONE stacked array
    ``[L, num_blocks, block_size, N_kv * Hd]``, handed to the writers and
    the kernel whole with the layer meant, never sliced
    (``ops.paged_attention``); a latent group's ``k_cache`` is a tuple of a
    plane ``[num_blocks, block_size, row]`` a layer, its ``v_cache`` ``()``.
    """
    paged: tuple[PagedGroup, ...]
    programs: str
    state: object | None = None
    program_prefix: str = ''
    dense_prefill: bool = True
    passes: int = 1  # runs of the stack a token, each with planes of its own
    block: int = 1  # positions a sequence decides together (models/sdar.py)

    @property
    def windowed(self) -> tuple[PagedGroup, ...]:
        return tuple(g for g in self.paged if g.window is not None)

    @property
    def latent(self) -> bool:
        """Whether a group declares a row of its own (no V pool)."""
        return any(g.row is not None for g in self.paged)


def layer_norm(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray | None,
    eps: float,
) -> jnp.ndarray:
    """LayerNorm; ``bias=None`` = scale-only (ESM-C's bias-free norms)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    normed = (x - mean) * jax.lax.rsqrt(var + eps)
    out = normed * scale
    if bias is not None:
        out = out + bias
    return out


def rms_norm(
    x: jnp.ndarray, scale: jnp.ndarray, eps: float, plus_one: bool = False
) -> jnp.ndarray:
    # Norm statistics in fp32 for bf16 activations (standard TPU practice).
    # ``plus_one``: the Gemma-family ``(1 + w)`` parameterization — the
    # checkpoint stores zero-centered weights and the forward adds 1
    # (HF ``GemmaRMSNorm``), so loaded weights stay byte-identical to HF.
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    w = scale.astype(jnp.float32)
    if plus_one:
        w = 1.0 + w
    return (normed * w).astype(dtype)


def dense(
    x: jnp.ndarray,
    kernel,
    bias: jnp.ndarray | None = None,
    qmm_backend: str | None = None,
) -> jnp.ndarray:
    """``x @ kernel (+ bias)`` with kernel laid out ``[in, out]``.

    ``kernel`` may be a quantized :class:`~distllm_tpu.ops.quantization.
    QTensor` — dequantization happens HERE, at the point of use, so a
    layer scan over a quantized tree only ever materializes one layer's
    bf16 weights at a time (dequantizing the whole stack outside the scan
    costs the full float model in HLO temps and OOMs 7B on 16 GiB HBM).

    int8 2-D kernels never dequantize at all: they route through
    :func:`distllm_tpu.ops.quantized_matmul.int8_dense`, which keeps the
    weight int8 across HBM (scale applied to the dot's OUTPUT, convert
    fused into the weight stream). Motivation and tier choice are in
    that module's docstring. ``qmm_backend`` pins the tier for THIS call;
    ``None`` falls back to the process default
    (``DISTLLM_QMM_BACKEND=auto|pallas|xla|interpret``, read at import) at
    trace time — serving paths that validated the tier up front (the
    engine's TP-mesh check) must pass their resolved value explicitly so a
    later process-global change cannot re-route traced-at-serve kernels.
    """
    if hasattr(kernel, 'dequantize'):
        if getattr(kernel, 'kind', None) == 'int8' and kernel.q.ndim == 2:
            from distllm_tpu.ops import quantized_matmul as _qmm

            y = _qmm.int8_dense(
                x, kernel.q, kernel.scale,
                backend=qmm_backend or _qmm.default_backend(),
            )
            if bias is not None:
                y = y + bias.astype(y.dtype)
            return y
        kernel = kernel.dequantize()
    y = jnp.einsum('...i,io->...o', x, kernel.astype(x.dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    """HF-'gelu': the exact erf form, at every dtype.

    Checkpoints trained with erf-GELU get erf-GELU — dtype does not change
    the activation math. Deployments that want the cheaper polynomial opt
    in explicitly with the ``'gelu_tanh'`` activation name (see
    :func:`gelu_tanh` for the trade).
    """
    return jax.nn.gelu(x, approximate=False)


def gelu_tanh(x: jnp.ndarray) -> jnp.ndarray:
    """Opt-in tanh-approximated GELU (the HF ``gelu_pytorch_tanh`` form).

    The exact erf lowers to a long VPU polynomial that costs 19% of a
    BERT-base embed forward on a v5e (MFU 0.622 exact vs 0.790 tanh in a
    2026-07-31 record on older code, in git history; not re-measured, a
    hypothesis). The tanh form's max
    deviation from erf-GELU is ~3e-3 near |x|=2 — the same order as bf16's
    representation step there, so it is a REAL (if small) numerics change,
    not a free lunch; that is why it is an explicit activation choice
    (``hidden_act='gelu_tanh'``) rather than something bf16 turns on
    implicitly.
    """
    return jax.nn.gelu(x, approximate=True)


def silu(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(x)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 logit soft-capping: ``tanh(x/cap)*cap`` (one home for the
    formula; used on attention scores and final logits)."""
    return jnp.tanh(x / cap) * cap


ACTIVATIONS: dict[str, Callable] = {
    'gelu': gelu,
    'gelu_tanh': gelu_tanh,
    'gelu_new': gelu_tanh,  # HF's historical alias for the tanh form
    'silu': silu,
    'relu': jax.nn.relu,
}


def split_heads(x: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """``[B, S, N*H] -> [B, S, N, H]``."""
    b, s, d = x.shape
    return x.reshape(b, s, num_heads, d // num_heads)


def merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    """``[B, S, N, H] -> [B, S, N*H]``."""
    b, s, n, h = x.shape
    return x.reshape(b, s, n * h)


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mask: jnp.ndarray | None = None,
    is_causal: bool = False,
    scale: float | None = None,
    logit_softcap: float | None = None,
) -> jnp.ndarray:
    """Scaled dot-product attention over ``[B, S, N, H]`` tensors.

    ``mask`` is a boolean ``[B, S_kv]`` key-validity mask (attention-mask
    semantics of the embed pipeline) or a broadcastable full
    ``[B, N, S_q, S_kv]`` boolean mask.

    ``logit_softcap`` (Gemma-2) applies ``tanh(s/cap)*cap`` to the scaled
    scores before masking; ``jax.nn.dot_product_attention`` has no such
    hook, so that path is an explicit einsum — XLA still fuses it, it just
    skips the flash-style kernel (acceptable: softcap models also need
    per-layer masks that the fused path cannot express).
    """
    if mask is not None and mask.ndim == 2:
        mask = mask[:, None, None, :].astype(bool)
    if logit_softcap is None:
        return jax.nn.dot_product_attention(
            q, k, v, mask=mask, is_causal=is_causal, scale=scale
        )
    assert not is_causal, 'softcap path expects an explicit mask'
    if k.shape[2] != q.shape[2]:  # GQA: expand KV heads to match q
        k = repeat_kv(k, q.shape[2] // k.shape[2])
        v = repeat_kv(v, q.shape[2] // v.shape[2])
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    # [B, S, N, H] -> scores [B, N, Sq, Skv] in fp32.
    scores = jnp.einsum(
        'bqnh,bknh->bnqk', q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    scores = softcap(scores, logit_softcap)
    if mask is not None:
        # Large-finite mask, not -inf (same trick as
        # jax.nn.dot_product_attention): a fully-masked PADDED query row
        # would softmax to NaN, and that row's NaN V then poisons every
        # valid query downstream through exact-zero x NaN products.
        scores = jnp.where(mask, scores, jnp.float32(-0.7 * 3.4e38))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum('bnqk,bknh->bqnh', probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rope_frequencies(
    head_dim: int,
    max_len: int,
    theta: float,
    rope_scaling: dict | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Precompute RoPE cos/sin tables ``[max_len, head_dim//2]`` (host-side).

    ``rope_scaling`` follows the HF config field: ``{'rope_type':
    'llama3', 'factor', 'low_freq_factor', 'high_freq_factor',
    'original_max_position_embeddings'}`` (Llama-3 frequency-banded
    interpolation), ``{'rope_type': 'linear', 'factor'}`` or ``{'rope_type':
    'yarn', 'factor', 'original_max_position_embeddings', 'beta_fast',
    'beta_slow', 'attention_factor'}`` (``head_dim`` is then the ROTATED
    width of a partially rotated head). Unknown
    types raise — silently ignoring a checkpoint's scaling would produce
    wrong positions for every token past the original context.
    """
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    scale = 1.0  # on cos and sin: YaRN's attention factor
    if rope_scaling:
        kind = rope_scaling.get('rope_type', rope_scaling.get('type'))
        if kind in (None, 'default'):
            pass  # HF's explicit no-op scaling entry
        elif kind == 'linear':
            inv_freq = inv_freq / float(rope_scaling['factor'])
        elif kind == 'llama3':
            # HF _compute_llama3_parameters: low-frequency bands scale by
            # 1/factor, high-frequency bands keep the base frequency, and
            # the middle band interpolates smoothly.
            factor = float(rope_scaling['factor'])
            low = float(rope_scaling['low_freq_factor'])
            high = float(rope_scaling['high_freq_factor'])
            orig = float(rope_scaling['original_max_position_embeddings'])
            wavelen = 2.0 * np.pi / inv_freq
            smooth = (orig / wavelen - low) / (high - low)
            smooth = np.clip(smooth, 0.0, 1.0)
            inv_freq = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        elif kind == 'yarn':
            # HF _compute_yarn_parameters: dims that turn more than
            # beta_fast times over the original context keep their
            # frequency, those that turn less than beta_slow times are
            # interpolated by 1/factor, a linear ramp between; cos and sin
            # carry the attention factor (0.1 ln(factor) + 1 when absent).
            factor = float(rope_scaling['factor'])
            orig = float(rope_scaling['original_max_position_embeddings'])
            fast = float(rope_scaling.get('beta_fast') or 32)
            slow = float(rope_scaling.get('beta_slow') or 1)
            scale = rope_scaling.get('attention_factor')
            scale = 0.1 * np.log(factor) + 1.0 if scale is None else float(scale)

            def turns_dim(turns):
                return head_dim * np.log(orig / (turns * 2 * np.pi)) / (
                    2 * np.log(theta)
                )

            low, high = turns_dim(fast), turns_dim(slow)
            if rope_scaling.get('truncate', True):
                low, high = np.floor(low), np.ceil(high)
            low, high = max(low, 0.0), min(high, head_dim - 1.0)
            if low == high:
                high += 0.001
            ramp = np.clip(
                (np.arange(head_dim // 2, dtype=np.float64) - low)
                / (high - low), 0.0, 1.0,
            )
            inv_freq = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
        else:
            raise NotImplementedError(
                f'rope_scaling type {kind!r} (supported: linear, llama3, yarn)'
            )
    t = np.arange(max_len, dtype=np.float64)
    freqs = np.outer(t, inv_freq)
    return (
        (np.cos(freqs) * scale).astype(np.float32),
        (np.sin(freqs) * scale).astype(np.float32),
    )


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray | None = None,
    *,
    interleaved: bool = False,
) -> jnp.ndarray:
    """Rotate ``[B, S, N, H]`` queries/keys by position.

    ``interleaved=True`` pairs dims ``(0,1),(2,3),...``; ``False`` pairs
    ``(i, i+H/2)`` — the HF rotate_half layout used by Llama/Mistral *and*
    ESM2 (parity tests pin this).
    """
    b, s, n, h = x.shape
    if positions is None:
        table_cos, table_sin = cos[:s], sin[:s]  # [S, H/2]
        table_cos = table_cos[None, :, None, :]
        table_sin = table_sin[None, :, None, :]
    else:
        table_cos = cos[positions][:, :, None, :]  # positions [B, S]
        table_sin = sin[positions][:, :, None, :]
    table_cos = table_cos.astype(x.dtype)
    table_sin = table_sin.astype(x.dtype)
    if interleaved:
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        r1 = x1 * table_cos - x2 * table_sin
        r2 = x2 * table_cos + x1 * table_sin
        return jnp.stack([r1, r2], axis=-1).reshape(b, s, n, h)
    x1 = x[..., : h // 2]
    x2 = x[..., h // 2 :]
    r1 = x1 * table_cos - x2 * table_sin
    r2 = x2 * table_cos + x1 * table_sin
    return jnp.concatenate([r1, r2], axis=-1)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """GQA: expand ``[B, S, N_kv, H]`` to ``[B, S, N_kv*n_rep, H]``."""
    if n_rep == 1:
        return x
    b, s, n, h = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, n, n_rep, h)).reshape(
        b, s, n * n_rep, h
    )


def stack_layers(per_layer: list[dict]) -> dict:
    """Stack a list of per-layer param dicts into one pytree with leading L."""
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs, axis=0), *per_layer)


def causal_mask(q_len: int, kv_len: int, offset: int = 0) -> jnp.ndarray:
    """Boolean ``[q_len, kv_len]`` causal mask; query i sees kv <= i+offset."""
    q_pos = jnp.arange(q_len)[:, None] + offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return kv_pos <= q_pos


# ------------------------------------------------ a walk over stacked layers
def layer_at(tree, i, skip=(), dynamic: bool | None = None):  # distlint: traced
    """Layer ``i`` of a stacked tree, without the leaves ``skip`` names (a
    bank: its slice would be a copy). A static ``i`` is a static slice, a traced
    one (or ``dynamic=True``: a scan's body and a run of one layer lower to one
    text) a dynamic slice. A static slice folds into its dot only until the
    compiler merges the layers' slices into one fusion that writes each out: a
    leaf held a layer an array (``unstack``, a tuple) gives element ``i``."""
    if dynamic is None:
        dynamic = not isinstance(i, int)
    if dynamic:
        pick = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)  # noqa: E731
    else:
        pick = lambda a: a[i]  # noqa: E731
    return jax.tree.map(
        pick, {n: t for n, t in tree.items() if n not in skip}, is_leaf=_a_tuple
    )


def once_a_kind(layer, kinds, name: str) -> dict:
    """``kind -> layer(*kind, *arrays)`` as one jitted function a kind of
    layer (``kinds``: tuples of strings). The serving programs walk their
    layers unrolled, but the layers of a kind have one shape: called through
    this, a kind is traced and lowered once a program, which calls it a
    layer (XLA inlines the calls); unrolled text was ten seconds of Python a
    program. ``name`` (``str.format`` over the kind) is the private
    function's name in the lowered module, so a part of the persistent
    compile cache's key."""

    def jitted(kind):
        def call(*arrays):
            return layer(*kind, *arrays)

        call.__name__ = name.format(*kind)
        return jax.jit(call)

    return {kind: jitted(kind) for kind in sorted(set(kinds))}


def embed(params, dtype, input_ids):  # distlint: traced
    """The embedding's rows of ``input_ids`` in the model's ``dtype``."""
    return jnp.asarray(params['embed'])[input_ids].astype(jnp.dtype(dtype))


def swiglu(x, gate, up, down):  # distlint: traced
    return dense(silu(dense(x, gate)) * dense(x, up), down)


def dense_mlp(x, mp):  # distlint: traced
    """A dense layer's SwiGLU MLP, under the scope its readers find it by."""
    with jax.named_scope('distllm.dense_mlp'):
        return swiglu(
            x, mp['gate']['kernel'], mp['up']['kernel'], mp['down']['kernel']
        )


def finish_layer(x, mixed, mp, eps: float, mlp, counted):  # distlint: traced
    """Residual of the mixer's output, then the MLP block behind its RMS
    norm: ``mlp(rows [T, H], counted [T])`` is the family's MLP of the
    layer and returns the rows and the layer's counts."""
    x = x + mixed
    normed = rms_norm(x, mp['mlp_ln']['scale'], eps)
    out, counts = mlp(normed.reshape(-1, normed.shape[-1]), counted.reshape(-1))
    return x + out.reshape(x.shape), counts


def last_token(hidden, tail_lens):  # distlint: traced
    """``hidden [B, S, H]`` at each row's last counted position, ``[B, 1,
    H]``: all a prefill's head reads (a pad row reads position 0)."""
    last_idx = jnp.maximum(tail_lens - 1, 0)
    return jnp.take_along_axis(hidden, last_idx[:, None, None], axis=1)


# ------------------------------------------------------- the decode window
def decode_window(  # distlint: traced
    core, input_ids, positions, context_lens, caches: tuple, block_tables,
    steps_left, temperature, top_p, min_p, top_k, seeds, *,
    num_steps: int, sampling_top_window: int, counts,
):
    """``num_steps`` fused decode+sample steps: every family's step scan
    (``mistral.decode_loop`` says what the operands are). ``core(ids,
    positions, context_lens, caches, tables, live) -> (logits [B, V],
    caches, counts)`` is one token of every row, a family's
    ``_decode_core``; ``caches`` a tuple of the pytrees the steps update in
    place; ``block_tables`` one table or a tuple of them, one a cache
    group; ``counts`` the zero of the family's counter, a pytree of arrays.

    A slot whose ``steps_left`` has run out is not ``live``: its table row
    reads 0 (its K/V writes go to the trash block), its id, position and
    context stay, and its sampled tokens are garbage the host discards;
    ``core`` gets ``live`` to keep such a row's state and to leave it out of
    its counts, which are summed over the steps. The token a step produces
    sits at absolute index ``pos + 1``, which folds into its row's sampling
    key. Returns ``(tokens [num_steps, B], caches, last_ids, counts)``."""
    from distllm_tpu.ops.sampling import fold_row_keys, sample_tokens

    def body(carry, _):
        ids, pos, ctx, *caches, live_steps, counts = carry
        live = live_steps > 0
        tables = jax.tree.map(lambda bt: jnp.where(live[:, None], bt, 0), block_tables)
        logits_, caches, step_counts = core(
            ids, pos, ctx, tuple(caches), tables, live
        )
        token = sample_tokens(
            logits_, None, temperature, top_p, min_p,
            top_window=sampling_top_window, top_k=top_k,
            row_keys=fold_row_keys(seeds, pos + 1),
        )
        ids = jnp.where(live, token, ids)
        pos = jnp.where(live, pos + 1, pos)
        ctx = jnp.where(live, ctx + 1, ctx)
        live_steps = live_steps - 1
        counts = jax.tree.map(jnp.add, counts, step_counts)
        return (ids, pos, ctx, *caches, live_steps, counts), token

    (ids, _, _, *caches, _, counts), tokens = jax.lax.scan(
        body,
        (
            input_ids, positions, context_lens, *caches,
            steps_left.astype(jnp.int32), counts,
        ),
        None,
        length=num_steps,
    )
    return tokens, tuple(caches), ids, counts


# ------------------------------------------------------- seeded parameters
def seeded_tree(
    rng: jax.Array, dtype, hidden: int, top: dict, trees: dict, wrap,
    scales=(), leaf=None,
) -> dict:
    """Random parameters made on the device by one jitted program, the key
    its ARGUMENT: normal(0, 0.02) in ``dtype`` where nothing else is said,
    ``final_ln`` a unit scale over ``hidden``.

    ``top``: ``name -> shape`` of the top-level matrices, drawn from
    ``fold_in(key, i)`` in the dict's order. ``trees``: ``kind -> (fold,
    count, {name: shape})``, the stacked trees: a tree's key is
    ``fold_in(key, fold)`` and leaf ``ni`` of its SORTED names draws
    ``[count, *shape]`` from ``fold_in(tree key, ni)``. ``scales`` names the
    leaves that are ones; ``leaf(name, key, shape, normal)`` returns a leaf
    that is neither that nor ``normal(key, shape)``, or None (``normal(key,
    shape, scale=0.02, dtype=dtype)``); ``wrap(name, leaf)`` gives a tree's
    leaf its place (``{'kernel': leaf}``). The benchmark's cells take their
    weights from these numbers: tests/test_family_scaffold.py pins the bits."""
    dtype = jnp.dtype(dtype)

    @jax.jit
    def build(key):
        def normal(key, shape, scale=0.02, dtype=dtype):
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

        def make(name, key, shape):
            if name in scales:
                return jnp.ones(shape, dtype)
            made = None if leaf is None else leaf(name, key, shape, normal)
            return normal(key, shape) if made is None else made

        params = {
            name: normal(jax.random.fold_in(key, i), shape)
            for i, (name, shape) in enumerate(top.items())
        }
        params['final_ln'] = {'scale': jnp.ones((hidden,), dtype)}
        for kind, (fold, count, shapes) in trees.items():
            tkey = jax.random.fold_in(key, fold)
            params[kind] = {
                name: wrap(
                    name,
                    make(name, jax.random.fold_in(tkey, ni), (count, *shape)),
                )
                for ni, (name, shape) in enumerate(sorted(shapes.items()))
            }
        return params

    return build(rng)


def tree_table(kinds, count, shapes, first: int = 8) -> dict:
    """``seeded_tree``'s ``trees`` for the ``kinds`` that have layers: a
    kind's fold-in number is ``first`` plus its place among ``kinds``."""
    return {
        kind: (first + ti, count(kind), shapes(kind))
        for ti, kind in enumerate(kinds) if count(kind)
    }


def tree_specs(top: dict, trees: dict, wrap, banks=()) -> dict:
    """``seeded_tree``'s tree as PartitionSpecs: the expert banks
    (``(kind, name)`` pairs) over ``expert``, everything else replicated."""
    specs = {name: P(*(None,) * len(shape)) for name, shape in top.items()}
    specs['final_ln'] = {'scale': P()}
    for kind, (_, _, shapes) in trees.items():
        specs[kind] = {
            name: wrap(
                name,
                P(None, 'expert', None, None) if (kind, name) in banks
                else P(*(None,) * (len(shape) + 1)),
            )
            for name, shape in shapes.items()
        }
    return specs


# At the file's end: no line above moves (a Pallas kernel's serialized body
# carries its callers' line numbers, ``decode_window``'s among them).
def conv_tail(window, tail_lens, keep: int):  # distlint: traced
    """The last ``keep`` counted rows of a causal convolution's input
    ``window [B, keep + S, C]`` (the carried rows, then the span's own)
    after each row's first ``tail_lens [B]`` positions: what the next span
    starts from; carried rows where a row counts fewer than ``keep``."""
    idx = tail_lens[:, None] + jnp.arange(keep)[None, :]
    return jnp.take_along_axis(window, idx[..., None], axis=1)


def _a_tuple(leaf) -> bool:
    return isinstance(leaf, tuple)


def unstack(stack, own: bool = False) -> tuple:
    """A stacked leaf ``[L, ...]`` as a tuple of its ``L`` layers, each an
    array of its own (``layer_at`` picks from either): the form a family's
    ``serving_params`` gives a leaf whose static slices the compiler would
    otherwise write out every step of an unrolled window. The layers are
    taken one at a time by one jitted program a shape (a host array's are
    its views); ``own`` deletes the stack behind the last of them, so that
    the two forms are both alive only for that long. Under a layer SCAN the
    index is traced (and under ``dynamic=True`` taken as if it were) and a
    tuple cannot be indexed by it: such a walk keeps its stacks."""
    if not isinstance(stack, jax.Array):
        return tuple(stack[i] for i in range(stack.shape[0]))
    layers = tuple(_layer_of(stack, i) for i in range(stack.shape[0]))
    if own:
        jax.block_until_ready(layers)
        stack.delete()
    return layers


@jax.jit
def _layer_of(stack, i):
    return jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)


# ------------------------------------------- a walk over two cache groups
# A decoder whose layers are of two attention kinds, full and windowed, each
# with a cache group of its own (``models/laguna.py``,
# ``models/smallthinker.py``): the serving programs take a pair of each cache
# operand in this order, a group's ``k_cache`` its stacked pool.
CACHE_GROUPS = ('full', 'window')


def layer_runs(kinds, trees=None) -> list[tuple]:
    """``(*kind, first index in each of the layer's trees, count)`` of every
    run of equal consecutive layers. ``kinds[l]`` is layer ``l``'s kind, a
    tuple of names; ``trees[l]`` names the stacked trees the layer's
    parameters lie in (the kind itself where None), a layer's index in a
    tree being the count of earlier layers that lie in it."""
    trees = kinds if trees is None else trees
    runs: list[list] = []
    seen: dict = {}
    for kind, of_layer in zip(kinds, trees):
        firsts = [seen.get(tree, 0) for tree in of_layer]
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, firsts, 1])
        for tree in of_layer:
            seen[tree] = seen.get(tree, 0) + 1
    return [(*kind, *firsts, count) for kind, firsts, count in runs]


def layer_indices(runs) -> list[tuple]:
    """``(*kind, index in each of the layer's trees)`` of every layer of
    ``layer_runs``' runs (a kind names as many things as a layer has
    trees)."""
    out = []
    for *names, count in runs:
        kind, firsts = names[: len(names) // 2], names[len(names) // 2:]
        out += [(*kind, *(first + i for first in firsts)) for i in range(count)]
    return out


def run_indices(*firsts_and_count):
    """A run's indices into its trees, one ``arange`` a tree: a scan's xs."""
    *firsts, count = firsts_and_count
    return tuple(
        jnp.arange(first, first + count, dtype=jnp.int32) for first in firsts
    )


def walk_cache_groups(  # distlint: traced
    layer, layers, name: str, x, k_cache, v_cache, block_tables, weights,
    rest, counts=None,
):
    """The serving programs' walk over the layers of two cache groups,
    unrolled, each layer a call of one jitted function a kind
    (``once_a_kind``) with static indices into the weights (a static slice
    of the stacked kernels folds into its matmul) and the layer's index in
    its group's pool a traced scalar: the pool goes in and comes back whole,
    written in that layer's pages.

    ``layers``: ``(*kind, index in the group's tree, index in the second
    tree)`` of every layer (``layer_indices``), ``kind[0]`` the layer's
    cache group. ``layer(*kind, x, *weights(kind, ai, mi), k_cache, v_cache,
    li, table, *rest(kind))`` returns ``(x, k_cache, v_cache)`` and, with
    ``counts`` (the zero of the family's counter), the layer's counts behind
    them. ``k_cache``, ``v_cache`` and ``block_tables`` are pairs in
    ``CACHE_GROUPS``' order. Returns ``(x, k_cache, v_cache)`` with the
    caches as the pairs they came in as, and the summed counts where
    asked."""
    tables = dict(zip(CACHE_GROUPS, block_tables))
    pools = dict(zip(CACHE_GROUPS, zip(k_cache, v_cache)))
    kinds = [entry[:-2] for entry in layers]
    layer_of = once_a_kind(layer, kinds, name)
    for *kind, ai, mi in layers:
        kind = tuple(kind)
        group = kind[0]
        x, k_pool, v_pool, *counted = layer_of[kind](
            x, *weights(kind, ai, mi), *pools[group], jnp.int32(ai),
            tables[group], *rest(kind),
        )
        pools[group] = (k_pool, v_pool)
        if counts is not None:
            counts = counts + counted[0]
    out = (
        x,
        tuple(pools[g][0] for g in CACHE_GROUPS),
        tuple(pools[g][1] for g in CACHE_GROUPS),
    )
    return out if counts is None else (*out, counts)
