"""The routed expert layer of a chip that holds a share of the experts.

``routed_experts`` ranks every expert the router knows, keeps the top-k of a
token, normalises the gate over those k, and computes only the (token,
expert) pairs whose expert is HELD here: the pairs are sorted by expert and
run through ``jax.lax.ragged_dot`` (a grouped matmul: each row is multiplied
by its own expert's kernel, never by all ``E_held``). A chip that holds a
share of the experts (``first_expert``, the bank's leading dim) adds nothing
for the others: in a deployment their chips add it.

Softmax over the k kept logits equals softmax over all experts renormalised
over the kept ones (Mixtral's published order). ``mixtral.moe_mlp`` is NOT a
caller: it runs under a mesh with its banks sharded over ``expert``, and the
TPU's grouped matmul is a kernel call that takes its operand whole, so the
partitioner gathers every chip's bank onto every chip for it (compiled for a
v5e 2x2 at Mixtral-8x7B's widths: three ``all-gather``s of ``bf16[8, 4096,
14336]``). Its dense einsum partitions over ``expert``; this layer does not
yet, which is why a hybrid model refuses a mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _bank(w, dtype):
    # Expert banks may arrive weight-only quantized (QTensor); the dequant
    # happens here, at the point of use, so only one layer's experts
    # materialise as floats at a time (same policy as common.dense).
    return (w.dequantize() if hasattr(w, 'dequantize') else w).astype(dtype)


def routed_experts(  # distlint: traced
    x: jnp.ndarray,  # [T, H]
    router_kernel: jnp.ndarray,  # [H, E_routed]
    gate: jnp.ndarray,  # [E_held, H, I]
    up: jnp.ndarray,  # [E_held, H, I]
    down: jnp.ndarray,  # [E_held, I, H]
    experts_per_token: int,
    first_expert: int = 0,
    counted: jnp.ndarray | None = None,  # [T] bool: rows that count
    layer=None,
    routed_scale: float = 1.0,
    scoring: str = 'softmax',
    select_bias: jnp.ndarray | None = None,  # [E_routed] float32
    norm_eps: float = 1e-20,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``sum_e g_e expert_e(x)`` over the held experts among a token's top-k.

    Returns ``(out [T, H], pairs [2] int32)``: the (token, expert) pairs
    routed and those of them held here, over the rows ``counted`` marks
    (all rows when None); the engine sums them over a window.

    With ``layer`` (an index, static or traced) the banks are a layer
    stack ``[L, E_held, ...]`` and the layer's experts are groups ``layer *
    E_held`` onward of ``L * E_held``, the other layers' groups empty: the
    grouped matmul is a kernel call that reads its operand whole, and a
    layer sliced out of the stack for it would be copied (216 MB a bank at
    Granite's widths, three banks a layer, every step).

    ``routed_scale`` multiplies the normalised gates (a family's
    ``routed_scaling_factor``). A shared expert is the caller's: every chip
    of the expert axis computes it alike, so it is added once, outside.

    ``scoring='sigmoid'`` (DeepSeek-V3's ``noaux_tc`` router): an expert's
    score is ``sigmoid(logit)``, the k kept are the largest of ``score +
    select_bias`` (the bias chooses and never weighs), and the gates are
    the kept SCORES over their sum plus ``norm_eps`` (the family's
    published normaliser: 1e-20 for DeepSeek-V3, the default, 1e-6 for
    ``lfm2_moe``). ``'softmax'`` with no bias is softmax over the k kept
    logits, as it was.
    """
    if scoring not in ('softmax', 'sigmoid'):
        raise ValueError(f'scoring must be softmax or sigmoid, got {scoring!r}')
    if scoring == 'softmax' and select_bias is not None:
        raise ValueError('a selection bias is implemented for sigmoid scoring')
    dtype = x.dtype
    tokens, k = x.shape[0], experts_per_token
    gate, up, down = _bank(gate, dtype), _bank(up, dtype), _bank(down, dtype)
    held = gate.shape[-3]
    if layer is not None:
        gate, up, down = (
            w.reshape(-1, *w.shape[2:]) for w in (gate, up, down)
        )
    with jax.named_scope('distllm.moe'):
        logits = jnp.einsum(
            'th,he->te', x.astype(jnp.float32),
            router_kernel.astype(jnp.float32),
        )
        if scoring == 'softmax':
            top_logits, top_idx = jax.lax.top_k(logits, k)
            weights = jax.nn.softmax(top_logits, axis=-1)  # [T, k] float32
        else:
            scores = jax.nn.sigmoid(logits)
            chosen_by = scores if select_bias is None else (
                scores + select_bias.astype(jnp.float32)
            )
            _, top_idx = jax.lax.top_k(chosen_by, k)
            kept = jnp.take_along_axis(scores, top_idx, axis=-1)
            weights = kept / (kept.sum(axis=-1, keepdims=True) + norm_eps)
        if routed_scale != 1.0:
            weights = weights * routed_scale
        local = top_idx - first_expert
        is_held = (local >= 0) & (local < held)
        # Pairs sorted by held expert; pairs of absent experts go last,
        # past the end of the last group, where ragged_dot computes nothing.
        group = jnp.where(is_held, local, held).reshape(-1)
        order = jnp.argsort(group, stable=True)
        group_sizes = jnp.bincount(group, length=held + 1)[:held].astype(
            jnp.int32
        )
        if layer is not None:
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((gate.shape[0],), jnp.int32), group_sizes,
                (layer * held,),
            )
        # Rows in whole sublane tiles of 8: the TPU's grouped matmul is
        # refused by the compiler for other counts (12, 20, 30 rows over 324
        # groups). The pad rows lie past the last group: never computed.
        rows = x[jnp.pad(order // k, (0, -tokens * k % 8))]  # [T*k (+pad), H]
        hidden = jax.nn.silu(
            jax.lax.ragged_dot(rows, gate, group_sizes)
        ) * jax.lax.ragged_dot(rows, up, group_sizes)
        out = jax.lax.ragged_dot(hidden, down, group_sizes)[: tokens * k]
        # where, not a product: rows past the last group are not computed.
        out = jnp.where(
            is_held.reshape(-1)[order][:, None],
            out.astype(jnp.float32) * weights.reshape(-1)[order][:, None],
            0.0,
        )
        # Back to token order, then the k pairs of a token add up.
        out = out[jnp.argsort(order)].reshape(tokens, k, -1).sum(axis=1)
        rows_counted = (
            jnp.ones((tokens,), bool) if counted is None else counted
        )
        pairs = jnp.stack([
            rows_counted.sum() * k,
            (is_held & rows_counted[:, None]).sum(),
        ]).astype(jnp.int32)
    return out.astype(dtype), pairs
