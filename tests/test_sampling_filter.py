"""The sampler's filter by threshold (``ops/sampling.py``): the kept set
against a float64 sort-and-cumsum reference, the survivor guarantee, the
drawn frequencies against the filtered softmax, the all-greedy branch, and
a guard that neither the sampler nor a decode window lowers to a sort."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.models import mistral
from distllm_tpu.ops.sampling import (
    _float_key,
    _key_float,
    filter_logits,
    fold_row_keys,
    sample_tokens,
)

# Rows of one batch: temperature 0 and positive mixed, every filter alone
# and together. (temperature, top_p, min_p, top_k)
ROWS = [
    (0.5, 0.95, 0.0, 0),
    (1.0, 0.5, 0.0, 0),
    (0.0, 0.5, 0.05, 40),  # greedy: argmax, whatever its filters say
    (0.7, 1.0, 0.05, 0),
    (1.0, 0.0001, 0.0, 0),
    (0.8, 0.95, 0.05, 40),
    (1.3, 1.0, 0.0, 1),
    (0.0, 1.0, 0.0, 0),
    (1.0, 0.5, 0.0, 40),
    (0.6, 1.0, 0.0, 0),
]


def _logits(kind: str, vocab: int, rows: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(rows, vocab)) * 2.0).astype(np.float32)
    if kind == 'bf16_ties':
        # What a bf16 LM head gives: 8 bits of mantissa, many equal values.
        x = np.array(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    return x


def _reference_keep(x, temperature, top_p, min_p, top_k, window,
                    margin=1e-5):
    """Sort, cumulative sum and rank cut of one row in float64, with ties
    kept whole. Returns ``(keep, decided)``: ``decided`` is False when a
    mass at the boundary lies within ``margin`` of ``top_p``, where the
    order of float32 additions may decide."""
    scaled = (x / np.float32(temperature)).astype(np.float64)
    probs = np.exp(scaled - scaled.max())
    probs /= probs.sum()
    order = np.argsort(-scaled, kind='stable')
    ranked = scaled[order]
    cumulative = np.cumsum(probs[order])
    keep = np.ones(scaled.shape, bool)
    decided = True
    if top_p < 1:
        cut = min(int((cumulative < top_p).sum()), len(ranked) - 1)
        keep &= scaled >= ranked[cut]
        decided = bool(np.all(np.abs(cumulative - top_p) > margin))
    if min_p > 0:
        keep &= scaled >= scaled.max() + np.log(min_p)
    cap = window
    if top_k > 0:
        cap = top_k if cap <= 0 else min(cap, top_k)
    if 0 < cap < len(ranked):
        keep &= scaled >= ranked[cap - 1]
    return keep, decided


def _columns(rows):
    t, p, m, k = (np.asarray(col) for col in zip(*rows))
    return (
        jnp.asarray(t, jnp.float32), jnp.asarray(p, jnp.float32),
        jnp.asarray(m, jnp.float32), jnp.asarray(k, jnp.int32),
    )


@pytest.mark.parametrize('window', [0, 8])
@pytest.mark.parametrize('kind', ['normal', 'bf16_ties'])
@pytest.mark.parametrize('vocab', [32768, 50176])
def test_kept_set_matches_float64_sort_reference(vocab, kind, window):
    x = _logits(kind, vocab, len(ROWS), seed=vocab + window)
    t, p, m, k = _columns(ROWS)
    filtered = np.asarray(
        jax.jit(filter_logits, static_argnames='top_window')(
            jnp.asarray(x), t, p, m, top_k=k, top_window=window
        )
    )
    checked = 0
    for row, (temp, top_p, min_p, top_k) in enumerate(ROWS):
        if temp <= 0:
            continue
        want, decided = _reference_keep(
            x[row], temp, top_p, min_p, top_k, window
        )
        got = np.isfinite(filtered[row])
        assert got.any()
        if not decided:
            continue
        checked += 1
        assert np.array_equal(got, want), (row, got.sum(), want.sum())
        # What survives is the scaled logit itself, in vocabulary order.
        np.testing.assert_array_equal(
            filtered[row][got], (x[row] / np.float32(temp))[got]
        )
    assert checked >= 6


@pytest.mark.parametrize('case', ['top_p', 'top_k', 'window'])
def test_boundary_on_a_tie_keeps_the_whole_tie(case):
    """The cutoff falls on a value four tokens share: all four stay (the
    sort cut a rank cap by index; top-p kept ties then as now)."""
    x = np.full((1, 64), -20.0, np.float32)
    x[0, [3, 17, 40]] = [4.0, 3.0, 2.5]
    x[0, [5, 9, 21, 33]] = 2.0  # the tie: ranks 4-7
    x[0, 50] = 1.0
    kw = {
        'top_p': dict(top_p=0.9, top_k=0, window=0),   # mass above 2.0: .86
        'top_k': dict(top_p=1.0, top_k=5, window=0),
        'window': dict(top_p=1.0, top_k=0, window=5),
    }[case]
    filtered = np.asarray(filter_logits(
        jnp.asarray(x), jnp.ones(1), jnp.full(1, kw['top_p']),
        jnp.zeros(1), top_k=jnp.full(1, kw['top_k'], jnp.int32),
        top_window=kw['window'],
    ))[0]
    assert sorted(np.flatnonzero(np.isfinite(filtered))) == [
        3, 5, 9, 17, 21, 33, 40,
    ]
    want, _ = _reference_keep(
        x[0], 1.0, kw['top_p'], 0.0, kw['top_k'], kw['window']
    )
    assert np.array_equal(np.isfinite(filtered), want)


@pytest.mark.parametrize('top_p,min_p,top_k', [
    (0.0, 0.0, 0), (1e-9, 0.0, 0), (1.0, 1.0, 0), (1.0, 5.0, 0),
    (1.0, 0.0, 1), (0.0001, 0.99, 1), (1.0, 0.0, 10**6),
])
def test_at_least_one_survivor_and_it_is_the_largest(top_p, min_p, top_k):
    x = _logits('bf16_ties', 4096, 3, seed=11)
    x[1] = 0.0  # a flat row: every token is the largest
    x[2, 7] = -np.inf  # a masked token never comes back
    filtered = np.asarray(filter_logits(
        jnp.asarray(x), jnp.full(3, 0.9), jnp.full(3, top_p),
        jnp.full(3, min_p), top_k=jnp.full(3, top_k, jnp.int32),
    ))
    kept = np.isfinite(filtered)
    assert kept.any(axis=-1).all()
    assert kept[np.arange(3), x.argmax(-1)].all()
    assert not kept[2, 7]
    if top_p >= 1 and min_p <= 0 and top_k > 4096:
        assert kept.sum() == 3 * 4096 - 1  # nothing filters: all stay


def test_float_keys_keep_the_order_and_round_trip():
    values = np.asarray(
        [-np.inf, -3.4e38, -1.0, -1e-30, -0.0, 0.0, 1e-30, 2.0, 3e38, np.inf],
        np.float32,
    )
    keys = np.asarray(_float_key(jnp.asarray(values)))
    assert (np.diff(keys.astype(np.int64)) > 0).all()
    back = np.asarray(_key_float(jnp.asarray(keys)))
    np.testing.assert_array_equal(back, values)
    assert np.signbit(back[4]) and not np.signbit(back[5])
    # Keys outside the reals' range clamp to the infinity on their side.
    ends = np.asarray(_key_float(jnp.asarray([0, 2**32 - 1], jnp.uint32)))
    assert ends.tolist() == [-np.inf, np.inf]


@pytest.mark.parametrize('top_p,min_p,top_k', [
    (0.9, 0.0, 0), (1.0, 0.1, 0), (1.0, 0.0, 5), (0.8, 0.02, 12),
])
def test_draw_frequencies_match_filtered_softmax(top_p, min_p, top_k):
    n, vocab = 20_000, 32
    row = np.random.default_rng(3).normal(size=vocab).astype(np.float32) * 1.5
    temp = 0.8
    logits = jnp.broadcast_to(jnp.asarray(row)[None, :], (n, vocab))
    ones = jnp.ones((n,), jnp.float32)
    tokens = np.asarray(jax.jit(sample_tokens)(
        logits, None, ones * temp, ones * top_p, ones * min_p,
        top_k=jnp.full((n,), top_k, jnp.int32),
        row_keys=fold_row_keys(
            jnp.arange(n, dtype=jnp.uint32), jnp.full((n,), 9, jnp.int32)
        ),
    ))
    keep, _ = _reference_keep(row, temp, top_p, min_p, top_k, 0)
    want = np.where(keep, np.exp((row / temp).astype(np.float64)), 0.0)
    want /= want.sum()
    counts = np.bincount(tokens, minlength=vocab)
    assert counts[~keep].sum() == 0
    chi2 = ((counts[keep] - want[keep] * n) ** 2 / (want[keep] * n)).sum()
    # 99.9th percentile of chi-square with at most 31 degrees of freedom.
    assert chi2 < 61.1, chi2


def test_all_greedy_batch_takes_argmax_and_agrees_with_mixed_batch():
    """Greedy rows get the same tokens through the ``cond``'s argmax
    branch (no sampled row in the batch) as inside a mixed batch, where
    filter and draw run beside them."""
    x = jnp.asarray(_logits('bf16_ties', 4096, 6, seed=5))
    seeds = jnp.arange(6, dtype=jnp.uint32)
    keys = fold_row_keys(seeds, jnp.full((6,), 3, jnp.int32))
    top_p, min_p = jnp.full(6, 0.9), jnp.zeros(6)
    top_k = jnp.zeros(6, jnp.int32)
    sample = jax.jit(sample_tokens)
    greedy = np.asarray(sample(
        x, None, jnp.zeros(6), top_p, min_p, top_k=top_k, row_keys=keys
    ))
    np.testing.assert_array_equal(greedy, np.asarray(x).argmax(-1))
    mixed_t = jnp.asarray([0.0, 0.9, 0.0, 0.0, 1.2, 0.0])
    mixed = np.asarray(sample(
        x, None, mixed_t, top_p, min_p, top_k=top_k, row_keys=keys
    ))
    np.testing.assert_array_equal(mixed[[0, 2, 3, 5]], greedy[[0, 2, 3, 5]])
    # The legacy batch-key path takes the same branches.
    legacy = np.asarray(sample(
        x, jax.random.PRNGKey(0), jnp.zeros(6), top_p, min_p
    ))
    np.testing.assert_array_equal(legacy, greedy)


def _sorts_in(lowered_text: str) -> list[str]:
    """Lines of a lowered module that sort or call a top-k (a gather's
    ``indices_are_sorted`` attribute is neither)."""
    text = lowered_text.replace('indices_are_sorted', '')
    return [
        line.strip() for line in text.splitlines()
        if any(word in line.lower() for word in ('sort', 'top_k', 'topk'))
    ]


def test_sampler_lowers_to_no_sort():
    """The mechanism, pinned where no chip is: neither ``stablehlo.sort``
    nor a top-k call in the lowered sampler at a real vocabulary."""
    b, vocab = 4, 32768
    lowered = jax.jit(
        lambda lg, t, p, m, k, seeds, counters: sample_tokens(
            lg, None, t, p, m, top_window=64, top_k=k,
            row_keys=fold_row_keys(seeds, counters),
        )
    ).lower(
        jax.ShapeDtypeStruct((b, vocab), jnp.float32),
        *(jax.ShapeDtypeStruct((b,), jnp.float32),) * 3,
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.uint32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
    )
    text = lowered.as_text()
    assert 'distllm.sample' in lowered.as_text(debug_info=True)
    assert not _sorts_in(text), _sorts_in(text)[:3]


def test_decode_window_lowers_to_no_sort():
    cfg = mistral.MistralConfig(
        vocab_size=512, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    b, blocks, block_size = 2, 8, 4
    cache = jnp.zeros(
        (cfg.num_layers, blocks, block_size,
         cfg.num_kv_heads * (cfg.hidden_size // cfg.num_heads)), jnp.float32,
    )
    ints = jnp.ones((b,), jnp.int32)
    floats = jnp.ones((b,), jnp.float32)
    lowered = jax.jit(
        lambda *args: mistral.decode_loop(
            params, cfg, *args, num_steps=4, max_table_positions=32
        )
    ).lower(
        ints, ints, cache, cache, jnp.ones((b, 4), jnp.int32), ints * 2,
        ints * 4, floats * 0.5, floats * 0.9, floats * 0.0, ints * 0,
        jnp.arange(b, dtype=jnp.uint32),
    )
    text = lowered.as_text()
    assert 'while' in text  # the window's scan and the bisection inside it
    assert not _sorts_in(text), _sorts_in(text)[:3]
