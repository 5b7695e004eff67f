"""Token sampling & speculative verification: temperature, top-p/top-k,
min-p, rejection sampling — vectorized and jitted.

Reference parity: vLLM ``SamplingParams`` as configured by
``generate/generators/vllm_backend.py:48-60`` (temperature, max_tokens, and
top_p XOR min_p; greedy when temperature == 0). All filtering happens on
fp32 logits; each sequence carries its own parameters so one decode batch can
mix sampling configs (continuous batching requirement).

This runs INSIDE the engine's fused decode scan (one sample per decode
step) and once a prefill dispatch, so it is written for the TPU hot path:
ONE implementation that never orders the vocabulary. Nothing the sampler
returns needs an order. The kept set of a row is ``{x >= t}`` for one value
``t``, the largest of three thresholds over the temperature-scaled logits
``x``:

- top-p: a token is in the nucleus iff the probability mass of the tokens
  strictly above it is under ``top_p``, so ``t_p`` is the smallest float
  ``v`` with ``sum(p[x > v]) < top_p``. That sum never rises with ``v``:
  a bisection over the order-preserving integer image of float32 finds
  ``t_p`` exactly in 32 halvings, each one fused compare, select and row
  sum over ``[B, V]``. Probabilities use the full-vocabulary logsumexp
  normalizer.
- min-p: ``prob >= min_p * max_prob  <=>  x >= max(x) + log(min_p)``, a
  pure log-space comparison.
- a rank cap (the request's ``top_k``, the engine's ``top_window``, the
  smaller where both are set; applied before top-p, probabilities against
  the full vocabulary): ``t_k`` is the value of the k-th largest logit, the
  same bisection on an integer count. Every token tied with that value is
  kept, which is vLLM's rule (it masks ``logits < kth value``); a sorted
  window would cut ties by index.

A sort (``lax.top_k`` over the vocabulary is XLA's multi-pass bitonic
network on the TPU, whatever ``k``) and the cumulative sum and gathers
behind it cost 5 ms a step at ``[96, 50176]``; a halving reads the row
once, from the chip's fast memory where XLA keeps it (6 us there). What a
dispatch does not need it does not run: no row with ``top_p < 1`` means a
top-p search of no passes, no row with a cap a rank search of no passes,
and a dispatch whose rows are all greedy (``temperature <= 0``) takes the
``argmax`` branch of a ``lax.cond`` and neither filters nor draws. The ops
carry the ``distllm.sample`` named scope in a device trace.

PRNG contract (docs/speculative.md "Sampled verification"): the draw for
the token at absolute sequence index ``i`` of a request uses
``fold_in(fold_in(PRNGKey(request_seed), i), tag)``. ``_ACCEPT_FOLD`` tags
the speculative accept/reject uniform; ``_SAMPLE_FOLD`` tags every
categorical draw (ordinary sampling, residual resampling, and the bonus
token). Because the key depends only on (request seed, token index), a
request's sampled stream is deterministic per (seed, schedule) and
identical across decode_window / mixed_window / spec_window dispatch. The
categorical's Gumbel noise rides on vocabulary position (it rode on rank
while the sampler sorted), so a stream is a function of (seed, schedule)
and of this layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_ACCEPT_FOLD = 1
_SAMPLE_FOLD = 2


def fold_row_keys(  # distlint: traced
    seeds: jnp.ndarray,  # [B] uint32 per-request seeds
    counters: jnp.ndarray,  # [B] int32 absolute token indices
    fold: int = _SAMPLE_FOLD,
) -> jax.Array:
    """Derive one PRNG key per row from (seed, token counter, tag).

    Counter-based rather than split-based: the key for a draw is a pure
    function of the request seed and the absolute index of the token being
    produced, so replays and cross-dispatch paths (decode scan vs. spec
    verify) agree bit-for-bit.
    """

    def one(seed, counter):
        key = jax.random.PRNGKey(seed)
        return jax.random.fold_in(jax.random.fold_in(key, counter), fold)

    return jax.vmap(one)(seeds, counters)


# Keys under the first and over the second stand for NaNs.
_NEG_INF_KEY = np.uint32(0x007FFFFF)
_POS_INF_KEY = np.uint32(0xFF800000)


def _float_key(x: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """The order-preserving uint32 image of float32: ``a < b`` as floats
    implies ``key(a) < key(b)`` (no NaN; ``-0.0`` is the key under
    ``+0.0``)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(
        bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000)
    )


def _key_float(key: jnp.ndarray) -> jnp.ndarray:  # distlint: traced
    """The float32 a key stands for; a key outside the reals' range is the
    infinity on its side."""
    key = jnp.clip(key, _NEG_INF_KEY, _POS_INF_KEY)
    bits = jnp.where(
        key >> 31 == 1, key & jnp.uint32(0x7FFFFFFF), ~key
    )
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _lowest_threshold(  # distlint: traced
    measure, limit: jnp.ndarray, searching: jnp.ndarray
) -> jnp.ndarray:
    """Per ``searching`` row, the key of the smallest float32 ``v`` with
    ``measure(v) < limit`` (all ones where no ``v`` has it); key 0 for the
    other rows, and no pass at all when no row searches.

    ``measure(v)`` is a ``[B]`` row sum over the tokens with ``x > v``: it
    never rises with ``v`` (the same reduction tree with more leaves
    zeroed), so the verdicts over the keys are False.. then True.., and 32
    halvings build the key from its top bit down. Each pass is one fused
    compare, select and row sum over ``[B, V]``. The skip is the loop's
    trip count, not a ``lax.cond`` around it: an operand of a conditional
    stays in HBM, and then every pass streams the row from there.
    """
    def one_pass(i, prefix):
        bit = jnp.uint32(1) << (31 - i).astype(jnp.uint32)
        # The largest key that leaves this bit clear.
        under = measure(_key_float(prefix | (bit - 1))) < limit
        return jnp.where(under | ~searching, prefix, prefix | bit)

    return jax.lax.fori_loop(
        0, jnp.where(jnp.any(searching), 32, 0), one_pass,
        jnp.zeros(limit.shape, jnp.uint32),
    )


def filter_logits(  # distlint: traced
    logits: jnp.ndarray,  # [B, V] fp32
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B] (1.0 disables)
    min_p: jnp.ndarray,  # [B] (0.0 disables)
    top_k: jnp.ndarray | None = None,  # [B] int32 (0 disables)
    top_window: int = 0,
) -> jnp.ndarray:
    """Temperature-scale and filter logits; shared by sampling and verify.

    Returns the temperature-scaled logits in VOCABULARY order, ``[B, V]``,
    with every filtered-out entry at ``-inf`` (categorical over a row
    samples the served distribution). The kept set is the one a descending
    sort and a cumulative sum give for top-p and min-p, ties included, up
    to the order in which float32 adds the masses; a rank cap (``top_k``,
    ``top_window``) keeps every token tied with the k-th largest value. At
    least one token always survives. Greedy rows (``temperature <= 0``)
    start no search; nobody reads what they hold beyond their ``argmax``.
    """
    vocab = logits.shape[-1]
    sampled = temperature > 0
    scaled = logits.astype(jnp.float32) / jnp.where(
        sampled, temperature, 1.0
    )[:, None]
    top = jnp.max(scaled, axis=-1)
    # min-p in log space; log(0) = -inf disables the filter.
    threshold = _float_key(top + jnp.log(jnp.maximum(min_p, 0.0)))

    # Exact probabilities: normalize against the whole vocabulary. The
    # masses are computed again in every pass (the select sits under the
    # exp), so the search streams one array, not two.
    lse = top + jnp.log(jnp.sum(jnp.exp(scaled - top[:, None]), axis=-1))

    def mass_above(v):
        log_probs = jnp.where(
            scaled > v[:, None], scaled - lse[:, None], -jnp.inf
        )
        return jnp.sum(jnp.exp(log_probs), axis=-1)

    # top_p >= 1 keeps everything outright: a sum that rounds to 1.0 can
    # drop nothing.
    threshold = jnp.maximum(
        threshold, _lowest_threshold(mass_above, top_p, sampled & (top_p < 1))
    )
    if top_k is not None or top_window > 0:
        cap = jnp.full(temperature.shape, top_window, jnp.int32)
        if top_k is not None:
            cap = jnp.where(
                (top_k > 0) & ((cap <= 0) | (top_k < cap)), top_k, cap
            )

        def count_above(v):
            return jnp.sum(scaled > v[:, None], axis=-1, dtype=jnp.int32)

        # The k-th largest value is the smallest v with under k tokens
        # above it.
        threshold = jnp.maximum(threshold, _lowest_threshold(
            count_above, cap, sampled & (cap > 0) & (cap < vocab)
        ))
    # The row's largest logit is above no threshold that can be asked for.
    threshold = _key_float(jnp.minimum(threshold, _float_key(top)))
    return jnp.where(scaled >= threshold[:, None], scaled, -jnp.inf)


def sample_tokens(  # distlint: traced
    logits: jnp.ndarray,  # [B, V] fp32
    key: jax.Array | None,
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B] (1.0 disables)
    min_p: jnp.ndarray,  # [B] (0.0 disables)
    top_window: int = 0,
    top_k: jnp.ndarray | None = None,  # [B] int32 (0 disables)
    row_keys: jax.Array | None = None,  # [B] keys from fold_row_keys
) -> jnp.ndarray:
    """Per-sequence sampling; temperature == 0 rows are greedy.

    ``top_window > 0`` caps the kept set at the tokens no smaller than the
    ``top_window``-th largest (see module docstring); ``0`` or ``>= V`` is
    no cap. With ``row_keys`` each row draws from its own counter-derived
    key (the engine's deterministic path); otherwise one batch ``key``
    feeds a single categorical (legacy path). A batch with no sampled row
    runs the ``argmax`` and neither filter nor draw.
    """
    def draw():
        filtered = filter_logits(
            logits, temperature, top_p, min_p, top_k=top_k,
            top_window=top_window,
        )
        if row_keys is not None:
            choice = jax.vmap(jax.random.categorical)(row_keys, filtered)
        else:
            choice = jax.random.categorical(key, filtered, axis=-1)
        return jnp.where(temperature > 0, choice.astype(jnp.int32), greedy)

    with jax.named_scope('distllm.sample'):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.lax.cond(jnp.any(temperature > 0), draw, lambda: greedy)


def sample_tokens_windowed(  # distlint: traced
    logits: jnp.ndarray,
    key: jax.Array | None,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    min_p: jnp.ndarray,
    top_window: int,
    top_k: jnp.ndarray | None = None,
    row_keys: jax.Array | None = None,
) -> jnp.ndarray:
    """Alias for :func:`sample_tokens` with an explicit window (kept for
    call sites that always window)."""
    return sample_tokens(
        logits, key, temperature, top_p, min_p,
        top_window=max(1, top_window), top_k=top_k, row_keys=row_keys,
    )


def verify_spans(  # distlint: traced
    span_logits: jnp.ndarray,  # [B, S, V] fp32, all_logits=True span scores
    span_ids: jnp.ndarray,  # [B, S] int32: [last committed, draft_1..m]
    span_lens: jnp.ndarray,  # [B] int32: 1 + m (0 = inactive row)
    span_positions: jnp.ndarray,  # [B, S] int32 absolute span positions
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    min_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32
    seeds: jnp.ndarray,  # [B] uint32 per-request seeds
    top_window: int = 0,
) -> jnp.ndarray:
    """Device-side speculative verification (rejection sampling).

    Standard speculative-sampling rule over the *served* (filtered target)
    distribution p̃ with the prompt-lookup point-mass proposal q: accept
    draft d_i with probability min(1, p̃(d_i)/q(d_i)) = p̃(d_i); on
    rejection sample the normalized positive residual (p̃ − q)+ — p̃ with
    the draft masked out — and stop the span. Greedy rows (temperature
    <= 0) keep the exact pre-existing argmax semantics bit-for-bit:
    out[i] = argmax and a draft is accepted iff it equals that argmax.

    Returns packed ``[B, S+1]`` int32: ``out`` tokens per span position
    followed by ``accept_len`` (number of leading accepted drafts, in
    [0, m]). The host emits ``out[0..accept_len]`` inclusive —
    ``out[accept_len]`` is the residual correction, or the bonus token
    sampled from the full filtered target when every draft was accepted.
    """
    b, s, vocab = span_logits.shape
    flat = span_logits.reshape(b * s, vocab)

    def rep(x):
        return jnp.repeat(x, s)

    with jax.named_scope('distllm.sample'):
        filtered = filter_logits(
            flat, rep(temperature), rep(top_p), rep(min_p), top_k=rep(top_k),
            top_window=top_window,
        ).reshape(b, s, vocab)
    # The token produced at span position i has absolute index pos_i + 1 —
    # the same counter the decode scan uses for that token, so sampled
    # streams agree across dispatch flavors.
    counters = (span_positions + 1).astype(jnp.int32).reshape(b * s)
    u_keys = fold_row_keys(rep(seeds), counters, _ACCEPT_FOLD)
    s_keys = fold_row_keys(rep(seeds), counters, _SAMPLE_FOLD)

    cand = jnp.argmax(span_logits, axis=-1)  # greedy candidate per position

    m = jnp.maximum(span_lens - 1, 0)  # drafts per row
    drafts = jnp.concatenate(
        [span_ids[:, 1:], jnp.zeros((b, 1), span_ids.dtype)], axis=1
    )
    pos_in_draft = jnp.arange(s)[None, :] < m[:, None]

    # log p̃(draft) under the filtered target; -inf when the draft fell
    # outside the kept set (q point mass outside supp(p̃) never accepts).
    logz = jax.scipy.special.logsumexp(filtered, axis=-1)
    draft_val = jnp.take_along_axis(filtered, drafts[:, :, None], axis=-1)
    log_p_draft = draft_val[:, :, 0] - logz

    u = jax.vmap(jax.random.uniform)(u_keys).reshape(b, s)
    sampled_row = temperature[:, None] > 0
    accept = jnp.where(sampled_row, u < jnp.exp(log_p_draft), cand == drafts)
    accept = accept & pos_in_draft

    # Residual (p̃ − q)+ for the point-mass q: p̃ with the draft masked out
    # (categorical renormalizes). The bonus slot (past the drafts) and rows
    # whose kept set is exactly {draft} — where acceptance is certain and
    # the residual is empty — sample the full filtered target instead.
    is_draft = jnp.arange(vocab)[None, None, :] == drafts[:, :, None]
    residual = jnp.where(is_draft, -jnp.inf, filtered)
    res_valid = jnp.any(jnp.isfinite(residual), axis=-1)
    use_residual = pos_in_draft & res_valid
    corr_src = jnp.where(use_residual[:, :, None], residual, filtered)
    corr_sampled = jax.vmap(jax.random.categorical)(
        s_keys, corr_src.reshape(b * s, vocab)
    ).reshape(b, s)
    correction = jnp.where(sampled_row, corr_sampled, cand)

    out = jnp.where(accept, drafts, correction).astype(jnp.int32)
    accept_len = jnp.sum(
        jnp.cumprod(accept.astype(jnp.int32), axis=-1), axis=-1
    ).astype(jnp.int32)
    return jnp.concatenate([out, accept_len[:, None]], axis=-1)


# --------------------------------------------- unmasking a block's positions
# Generation by diffusion over blocks (``models/sdar.py``): a forward gives a
# candidate and a confidence for every position of a row's block, and a rule
# decides which masked positions take their candidate. At the file's end: no
# line above moves.
def sample_tokens_confidence(  # distlint: traced
    logits: jnp.ndarray,  # [R, V] fp32: a row a POSITION
    temperature: jnp.ndarray,  # [R]
    top_p: jnp.ndarray,  # [R] (1.0 disables)
    min_p: jnp.ndarray,  # [R] (0.0 disables)
    top_window: int = 0,
    top_k: jnp.ndarray | None = None,  # [R] int32 (0 disables)
    row_keys: jax.Array | None = None,  # [R] keys from fold_row_keys
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`sample_tokens`' twin that also returns the probability the
    kept token had: ``(tokens [R] int32, confidence [R] float32)``.

    A sampled row (``temperature > 0``) draws from its filtered distribution
    (:func:`filter_logits`: temperature, rank cap, top-p, min-p) and its
    confidence is the drawn token's probability UNDER THAT distribution (the
    kept set renormalised). A greedy row takes the ``argmax`` and its
    confidence is that token's softmax probability over the whole vocabulary
    at temperature 1: the filtered distribution of a greedy row is a point
    mass, which would rank nothing. A batch with no sampled row neither
    filters nor draws, as in :func:`sample_tokens`, whose bits stay its own.
    """
    logits = logits.astype(jnp.float32)
    top = jnp.max(logits, axis=-1)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # softmax at the argmax: 1 / sum(exp(x - max))
    greedy_conf = 1.0 / jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)

    def draw():
        filtered = filter_logits(
            logits, temperature, top_p, min_p, top_k=top_k,
            top_window=top_window,
        )
        choice = jax.vmap(jax.random.categorical)(row_keys, filtered)
        kept = jnp.take_along_axis(filtered, choice[:, None], axis=-1)[:, 0]
        conf = jnp.exp(kept - jax.scipy.special.logsumexp(filtered, axis=-1))
        sampled = temperature > 0
        return (
            jnp.where(sampled, choice.astype(jnp.int32), greedy),
            jnp.where(sampled, conf, greedy_conf),
        )

    return jax.lax.cond(
        jnp.any(temperature > 0), draw, lambda: (greedy, greedy_conf)
    )


def select_unmask(  # distlint: traced
    confidence: jnp.ndarray,  # [R, B] float32
    masked: jnp.ndarray,  # [R, B] bool: positions still undecided
    count,  # int or int32 scalar: positions the schedule decides this step
    threshold: jnp.ndarray | None = None,  # [R] float32, or None
) -> jnp.ndarray:
    """The positions a denoise step decides, ``[R, B]`` bool: among a row's
    masked positions the ``count`` of highest confidence, ties to the lower
    position (``low_confidence_static``); with a ``threshold`` every masked
    position whose confidence is OVER it where those are more than ``count``
    (``low_confidence_dynamic``; a threshold of 1.0 or more never is). A
    row with fewer masked positions than ``count`` decides them all. A
    block is a handful of positions: the ranks are the pairwise
    comparisons, no sort."""
    c = jnp.where(masked, confidence, -jnp.inf)
    idx = jnp.arange(c.shape[-1])
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (idx[None, :] < idx[:, None])[None]
    )  # [R, i, j]: j goes before i
    rank = jnp.sum(ahead & masked[:, None, :], axis=-1)
    chosen = masked & (rank < count)
    if threshold is None:
        return chosen
    over = masked & (confidence > threshold[:, None])
    return jnp.where(
        (jnp.sum(over, axis=-1) > count)[:, None], over, chosen
    )
