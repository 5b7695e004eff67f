"""The routed expert layer of a chip that holds a share of the experts.

``routed_experts`` ranks every expert the router knows, keeps the top-k of a
token, normalises the gate over those k, and computes only the (token,
expert) pairs whose expert is HELD here. A chip that holds a share of the
experts (``first_expert``, the bank's leading dim) adds nothing for the
others: in a deployment their chips add it.

The three expert matmuls take one of two forms, chosen by ``expert_form``
from the call's static shapes alone:

* ``grouped`` (a prefill dispatch: 512-2,048 tokens): the pairs are sorted
  by expert and run through a grouped matmul (each row is multiplied by its
  own expert's kernel, never by all ``E_held``), then a token's k held
  pairs are gathered back in the rows' dtype, weighted in float32 and
  summed in one pass (``combine``). On a TPU the grouped matmul is the
  repo's Pallas kernel (``ops/grouped_matmul.py``: one layer's groups, the
  tiles that hold a held pair, tiles from ``grouped_tiles``); on any other
  backend ``jax.lax.ragged_dot`` (``grouped_backend``).
* ``dense`` (a decode window: a handful of rows): every row is multiplied by
  EVERY held expert, one batched ``dot`` a bank, with the gate zero where a
  token did not choose an expert. Multiplying ``T`` rows by a bank costs
  ``2 T`` operations a weight, reading the bank 2 bytes a weight, and the
  chip does ``RIDGE_ROWS`` = 240 operations in the time it reads a byte: at
  48-96 rows the arithmetic hides under the bank's stream, which nearly
  every held expert needed anyway (12 pairs an expert at 96 rows over 16 of
  32), and a plain ``dot`` streams its weights near the HBM rate where the
  grouped kernel reads the same bytes at about half of it (``PERF.md``
  section 6, PR 40: the sweep behind the rule's two constants). No sort, no
  gather, no scatter; the same pairs, gates and counts.

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

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distllm_tpu.ops import grouped_matmul


def _bank(w, dtype):
    # Expert banks may arrive weight-only quantized (QTensor); the dequant
    # happens here, at the point of use, so only one layer's experts
    # materialise as floats at a time (same policy as common.dense).
    return (w.dequantize() if hasattr(w, 'dequantize') else w).astype(dtype)


# Rows at which multiplying by a bf16 weight costs what reading it does: a
# TPU v5e does 197e12 operations and reads 819e9 bytes a second, a row
# spends 2 operations on a weight of 2 bytes, so 197e12 / 819e9 = 240 rows.
# A constant, not a device query: a program compiled for a described chip
# and the tests on the CPU take the form the chip will run.
RIDGE_ROWS = 240
# The dense form's arithmetic may take this share of the bank's stream
# time (120 rows), and at least this share of the held experts is expected
# to have a pair (what the grouped form could have skipped is the rest).
# Both from the kernel-alone sweep of PERF.md section 6 (PR 40): dense read
# 1.9x faster at 96 rows and still ahead at 512, so the first is not a
# crossover but a fence: at 121-128 rows (one whole 128-row tile) the TPU
# compiler turns the WHOLE bank stack over for this dot (granite's 9-layer
# prefill scan: three ``bf16[9, 36, 4096, 768]`` copies hoisted out of the
# loop, 5.7 GB the chip does not have), which no constraint on the operand
# cured without a copy of its own.
DENSE_ARITHMETIC_SHARE = 0.5
DENSE_MIN_HELD_SHARE = 0.7


def expert_form(
    tokens: int, k: int, held: int, routed: int, hidden: int, width: int
) -> str:
    """``'dense'`` or ``'grouped'``: the form of the three expert matmuls
    for a call of ``tokens`` rows that keep ``k`` of ``routed`` experts,
    ``held`` of them here as ``[hidden, width]`` kernels. Pure: static
    shapes and the chip's ridge, nothing else.

    Dense when (a) multiplying every row by every held expert stays under
    ``DENSE_ARITHMETIC_SHARE`` of the time the banks take to stream, and
    (b) the grouped form had little to skip: a held expert is expected to
    have a pair with probability ``1 - (1 - k / routed) ** tokens`` (tokens
    choose alike and independently: a sizing, not a promise).
    """
    weights = 3 * held * hidden * width
    arithmetic = 2 * tokens * weights / RIDGE_ROWS  # in byte-times
    stream = 2 * weights
    with_pair = 1.0 - (1.0 - min(1.0, k / routed)) ** tokens
    if (
        arithmetic <= DENSE_ARITHMETIC_SHARE * stream
        and with_pair >= DENSE_MIN_HELD_SHARE
    ):
        return 'dense'
    return 'grouped'


def grouped_backend() -> str:
    """What runs the grouped form's three matmuls: ``'pallas'`` (the
    repo's kernel) on a TPU, ``'xla'`` (``jax.lax.ragged_dot``) elsewhere.
    ``'interpret'`` is the kernel on the Pallas interpreter: the tests set
    it, as they set ``'pallas'`` to compile for a described chip."""
    return 'pallas' if jax.default_backend() == 'tpu' else 'xla'


def grouped_tiles(
    tokens: int, k: int, hidden: int, width: int
) -> tuple[int, int, int] | None:
    """The kernel's tiles for a grouped call of ``tokens`` rows that keep
    ``k`` experts of ``[hidden, width]`` kernels (row tile, gate/up and
    down column tiles: ``ops.grouped_matmul.grouped_tiles`` over the call's
    pairs), or None where ``ragged_dot`` runs it. Pure but for the
    backend."""
    if grouped_backend() == 'xla':
        return None
    return grouped_matmul.grouped_tiles(tokens * k, hidden, width)


def bank_widths(params) -> tuple[int, int, int, int] | None:
    """``(E_held, E_routed, H, I)`` of a parameter tree's routed experts
    (the first dict that holds a ``router`` beside a ``gate`` bank, stacked
    over layers or not), or None: what ``expert_form`` wants of a model."""
    if not isinstance(params, dict):
        return None
    if 'router' in params and 'gate' in params:
        held, hidden, width = jax.tree.leaves(params['gate'])[0].shape[-3:]
        routed = jax.tree.leaves(params['router'])[0].shape[-1]
        return held, routed, hidden, width
    for child in params.values():
        widths = bank_widths(child)
        if widths is not None:
            return widths
    return None


ACTIVATIONS = {'silu': jax.nn.silu, 'relu': jax.nn.relu}


class Ranking(NamedTuple):
    """What the expert matmuls take of the router (``rank_experts``)."""

    weights: jnp.ndarray  # [T, k] float32: the kept gates, scaled
    local: jnp.ndarray  # [T, k]: a kept expert's index in the held bank
    is_held: jnp.ndarray  # [T, k] bool: whether that index is in the bank
    # The grouped form alone (None in the dense form):
    order: jnp.ndarray | None  # [T * k]: the pairs sorted by held expert
    group_sizes: jnp.ndarray | None  # int32: rows a group of the matmul


def rank_experts(  # distlint: traced
    x: jnp.ndarray,  # [T, H]: what the ROUTER reads
    router_kernel: jnp.ndarray,  # [H, E_routed]
    experts_per_token: int,
    bank_shape: tuple[int, ...],  # of ``gate``: [(L,) E_held, H, I]
    first_expert: int = 0,
    layer=None,
    routed_scale: float = 1.0,
    scoring: str = 'softmax',
    select_bias: jnp.ndarray | None = None,
    norm_eps: float = 1e-20,
) -> Ranking:
    """The ranking as a step of its own: the router's logits, the k kept, the
    gates, which of them are held in a bank of ``bank_shape`` and, where
    ``expert_form`` says ``grouped`` for these shapes, the pairs' sort and
    the group sizes. ``routed_experts`` makes it itself, inside its scope,
    unless it is handed one (``ranking=``): a family whose router reads
    another tensor than its experts calls this where that tensor is, under
    a scope of its own, with the arguments it gives ``routed_experts``."""
    form = expert_form(
        x.shape[0], experts_per_token, bank_shape[-3],
        router_kernel.shape[-1], *bank_shape[-2:],
    )
    return _rank(
        x, router_kernel, experts_per_token, bank_shape, first_expert, layer,
        routed_scale, scoring, select_bias, norm_eps, form,
    )


def _rank(
    x, router_kernel, k, bank_shape, first_expert, layer, routed_scale,
    scoring, select_bias, norm_eps, form,
) -> Ranking:
    if scoring not in ('softmax', 'sigmoid'):
        raise ValueError(f'scoring must be softmax or sigmoid, got {scoring!r}')
    if scoring == 'softmax' and select_bias is not None:
        raise ValueError('a selection bias is implemented for sigmoid scoring')
    held = bank_shape[-3]
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
    if form == 'dense':
        return Ranking(weights, local, is_held, None, None)
    # Pairs sorted by held expert; pairs of absent experts go last,
    # past the end of the last group, where the matmul computes nothing.
    group = jnp.where(is_held, local, held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    group_sizes = jnp.bincount(group, length=held + 1)[:held].astype(
        jnp.int32
    )
    if layer is not None and grouped_backend() == 'xla':
        # ``ragged_dot`` takes every group of the stack: the other layers'
        # are empty (the kernel adds the layer to its bank index instead).
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((bank_shape[0] * held,), jnp.int32), group_sizes,
            (layer * held,),
        )
    return Ranking(weights, local, is_held, order, group_sizes)


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
    activation: str = 'silu',
    ranking: 'Ranking | None' = None,
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

    ``activation`` (``'silu'`` | ``'relu'``, static) is the non-linearity on
    an expert's gate product, in all three forms. ``ranking`` is
    ``rank_experts``' result for these banks where the caller ranked ahead
    (a router that reads another tensor than the experts do:
    ``models/smallthinker.py``); the router's operands are then not read
    here (``router_kernel`` may be None) and the form is the one the ranking
    was made for. Without it the ranking is made here, from ``x``.
    """
    if activation not in ACTIVATIONS:
        raise ValueError(
            f'activation must be one of {sorted(ACTIVATIONS)}, got {activation!r}'
        )
    dtype = x.dtype
    tokens, k = x.shape[0], experts_per_token
    gate, up, down = _bank(gate, dtype), _bank(up, dtype), _bank(down, dtype)
    bank_shape = gate.shape
    if ranking is None:
        form = expert_form(
            tokens, k, bank_shape[-3], router_kernel.shape[-1],
            *bank_shape[-2:],
        )
    else:  # the form the ranking was made for
        form = 'dense' if ranking.order is None else 'grouped'
    if layer is not None and form == 'grouped':
        gate, up, down = (
            w.reshape(-1, *w.shape[2:]) for w in (gate, up, down)
        )
    with jax.named_scope('distllm.moe'):
        if ranking is None:
            ranking = _rank(
                x, router_kernel, k, bank_shape, first_expert, layer,
                routed_scale, scoring, select_bias, norm_eps, form,
            )
        weights, local, is_held = ranking[:3]
        if form == 'dense':
            out = _dense(x, gate, up, down, local, weights, layer, activation)
        else:
            out = _grouped(
                x, gate, up, down, ranking,
                grouped_tiles(tokens, k, *gate.shape[-2:]), layer, activation,
            )
        rows_counted = (
            jnp.ones((tokens,), bool) if counted is None else counted
        )
        pairs = jnp.stack([
            rows_counted.sum() * k,
            (is_held & rows_counted[:, None]).sum(),
        ]).astype(jnp.int32)
    return out.astype(dtype), pairs


def _grouped(x, gate, up, down, ranking, tiles, layer, activation):
    """The held pairs sorted by expert through the grouped matmul, each
    times its gate and a token's k added up (``combine``): float32 ``[T,
    H]``. With ``layer`` the banks are the stack's ``L * E_held`` groups and
    the layer's experts are groups ``layer * E_held`` onward: the kernel
    (``tiles``) adds the layer to its bank index, ``ragged_dot`` (no tiles)
    takes every group, the other layers' empty (``_rank`` made the sizes
    so)."""
    weights, _, is_held, order, group_sizes = ranking
    tokens, k = is_held.shape
    # Rows in whole tiles: the kernel's row tile, or sublane tiles of 8 (the
    # TPU's ragged_dot is refused by the compiler for other counts: 12, 20,
    # 30 rows over 324 groups). The pad rows lie past the last group: never
    # computed.
    whole = 8 if tiles is None else tiles[0]
    rows = x[jnp.pad(order // k, (0, -tokens * k % whole))]  # [T*k (+pad), H]
    if tiles is None:
        hidden = ACTIVATIONS[activation](
            jax.lax.ragged_dot(rows, gate, group_sizes)
        ) * jax.lax.ragged_dot(rows, up, group_sizes)
        out = jax.lax.ragged_dot(hidden, down, group_sizes)
    else:
        out = grouped_matmul.expert_matmuls(
            rows, gate, up, down, group_sizes, 0 if layer is None else layer,
            tiles=tiles, interpret=grouped_backend() == 'interpret',
            activation=activation,
        )
    # A pair's row of the sorted order, -1 where its expert is held
    # elsewhere: that row is never computed, and never read.
    place = jnp.where(is_held, jnp.argsort(order).reshape(tokens, k), -1)
    return combine(out, place, weights)


def combine(rows, place, weights):
    """``sum_j weights[t, j] * rows[place[t, j]]`` over a token's held pairs
    (``place`` -1: held elsewhere), float32 ``[T, H]``. ONE pass behind the
    matmuls: the k rows of a token are gathered in the rows' dtype as ``[T,
    k, H]``, and the gate, the ``where`` and the sum over k fuse behind the
    gather, so that no ``[pairs, H]`` array is made in float32 (weighting
    in sorted order first costs three such passes: ``PERF.md`` section 6,
    PR 43)."""
    pairs = rows[jnp.maximum(place, 0)].astype(jnp.float32)  # [T, k, H]
    # where, not a product: rows past the last group are not computed.
    return jnp.sum(
        jnp.where((place >= 0)[..., None], pairs * weights[..., None], 0.0),
        axis=1,
    )


def _dense(x, gate, up, down, local, weights, layer, activation):
    """Every row through every held expert, one batched ``dot`` a bank:
    float32 ``[T, H]``. With ``layer`` the banks are ``[L, E_held, ...]``
    and the layer is an index into the ``dot``'s operand, never a copy."""
    held = gate.shape[-3]
    if layer is not None:
        gate, up, down = (
            jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
            for w in (gate, up, down)
        )
    # [T, E_held] float32: a token's gate in the column of each held
    # expert it chose (the k kept are distinct), zero elsewhere; an expert
    # held elsewhere matches no column.
    w = jnp.sum(
        jnp.where(
            local[:, :, None] == jnp.arange(held), weights[:, :, None], 0.0
        ),
        axis=1,
    )
    hidden = ACTIVATIONS[activation](
        jnp.einsum('th,ehi->eti', x, gate)
    ) * jnp.einsum('th,ehi->eti', x, up)
    out = jnp.einsum('eti,eih->eth', hidden, down)
    return jnp.einsum('eth,te->th', out.astype(jnp.float32), w)
