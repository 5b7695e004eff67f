"""The engine's units: paged attention and the pool's writers, sampling
(exact and windowed), the pool's container, and the
engine's writers and readers over the stacked pool with a layer named."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.generate.engine import EngineConfig, LLMEngine, SamplingParams
from distllm_tpu.generate.engine.kv_cache import PagedKVCache
from distllm_tpu.models import mistral
from distllm_tpu.ops.paged_attention import (
    paged_attention_xla,
    write_prefill_kv,
    write_token_kv,
)
from distllm_tpu.ops.sampling import sample_tokens
from test_engine import _dense_greedy_reference


# ------------------------------------------------------------ paged attn
def _random_cache(rng, num_blocks=8, block_size=4, nkv=2, hd=8):
    """Head-folded, as the pool stores a layer."""
    k = rng.normal(size=(num_blocks, block_size, nkv * hd)).astype(np.float32)
    v = rng.normal(size=(num_blocks, block_size, nkv * hd)).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v)


def _heads(rows, nkv=2):
    """Rows taken out of a cache, ``[.., nkv * hd] -> [.., nkv, hd]``."""
    rows = np.asarray(rows)
    return rows.reshape(*rows.shape[:-1], nkv, -1)


def _dense_reference(q, k, v, context_len):
    """Plain attention over the first context_len tokens (GQA)."""
    num_heads, hd = q.shape
    nkv = k.shape[1]
    group = num_heads // nkv
    qg = q.reshape(nkv, group, hd)
    k = k[:context_len]
    v = v[:context_len]
    scores = np.einsum('kgd,tkd->kgt', qg, k) / np.sqrt(hd)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum('kgt,tkd->kgd', probs, v).reshape(num_heads, hd)


def test_paged_attention_matches_dense(rng):
    block_size = 4
    k_cache, v_cache = _random_cache(rng, block_size=block_size)
    # seq 0 uses blocks [2, 5] with 6 tokens; seq 1 uses [7] with 3 tokens.
    block_tables = jnp.asarray([[2, 5], [7, 0]], dtype=jnp.int32)
    context_lens = jnp.asarray([6, 3], dtype=jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 4, 8)).astype(np.float32))

    out = np.asarray(
        paged_attention_xla(q, k_cache, v_cache, block_tables, context_lens)
    )

    for seq, (blocks, ctx) in enumerate([((2, 5), 6), ((7,), 3)]):
        k_lin = np.concatenate([_heads(k_cache[b]) for b in blocks])
        v_lin = np.concatenate([_heads(v_cache[b]) for b in blocks])
        ref = _dense_reference(np.asarray(q[seq]), k_lin, v_lin, ctx)
        np.testing.assert_allclose(out[seq], ref, atol=1e-5, rtol=1e-4)


def test_paged_attention_pallas_interpret_matches_xla(rng):
    from distllm_tpu.ops.paged_attention import decode_attention

    k_cache, v_cache = _random_cache(rng, num_blocks=8, block_size=4)
    block_tables = jnp.asarray([[2, 5], [7, 0]], dtype=jnp.int32)
    context_lens = jnp.asarray([6, 3], dtype=jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 4, 8)).astype(np.float32))
    ref = np.asarray(
        paged_attention_xla(q, k_cache, v_cache, block_tables, context_lens)
    )
    out = np.asarray(
        decode_attention(
            q, k_cache, v_cache, block_tables, context_lens,
            context_lens - 1, backend='interpret',
        )
    )
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)


def test_write_token_and_prefill_kv(rng):
    k_cache = jnp.zeros((4, 4, 2 * 3))
    v_cache = jnp.zeros((4, 4, 2 * 3))
    # prefill 6 tokens into blocks [1, 2] (padded seq of 8)
    k_seq = jnp.asarray(rng.normal(size=(8, 2, 3)).astype(np.float32))
    v_seq = jnp.asarray(rng.normal(size=(8, 2, 3)).astype(np.float32))
    row = jnp.asarray([1, 2, 0, 0], dtype=jnp.int32)
    k_cache, v_cache = write_prefill_kv(
        k_cache, v_cache, k_seq, v_seq, row, jnp.int32(6)
    )
    np.testing.assert_allclose(_heads(k_cache[1]), np.asarray(k_seq[:4]))
    np.testing.assert_allclose(_heads(k_cache[2][:2]), np.asarray(k_seq[4:6]))
    # slot beyond length stays zero (trash block ate the padding)
    np.testing.assert_allclose(np.asarray(k_cache[2][2:]), 0.0)

    # token write at position 6 -> block row[6//4]=2, offset 2
    new_k = jnp.ones((1, 2, 3))
    new_v = jnp.ones((1, 2, 3)) * 2
    k_cache, v_cache = write_token_kv(
        k_cache, v_cache, new_k, new_v,
        jnp.asarray([[1, 2, 0, 0]], dtype=jnp.int32),
        jnp.asarray([6], dtype=jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(k_cache[2][2]), 1.0)
    np.testing.assert_allclose(np.asarray(v_cache[2][2]), 2.0)


# -------------------------------------------------------------- sampling
def test_sampling_greedy():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [3.0, 0.0, 0.1]])
    toks = sample_tokens(
        logits,
        jax.random.PRNGKey(0),
        temperature=jnp.zeros(2),
        top_p=jnp.ones(2),
        min_p=jnp.zeros(2),
    )
    assert list(np.asarray(toks)) == [1, 0]


def test_sampling_top_p_restricts_support():
    # One dominant token (p≈0.87); top_p=0.5 must always pick it.
    logits = jnp.tile(jnp.asarray([[4.0, 2.0, 0.0, -1.0]]), (64, 1))
    toks = sample_tokens(
        logits,
        jax.random.PRNGKey(1),
        temperature=jnp.ones(64),
        top_p=jnp.full(64, 0.5),
        min_p=jnp.zeros(64),
    )
    assert set(np.asarray(toks).tolist()) == {0}


def test_sampling_min_p_restricts_support():
    logits = jnp.tile(jnp.asarray([[4.0, 3.5, -8.0, -9.0]]), (128, 1))
    toks = np.asarray(
        sample_tokens(
            logits,
            jax.random.PRNGKey(2),
            temperature=jnp.ones(128),
            top_p=jnp.ones(128),
            min_p=jnp.full(128, 0.2),
        )
    )
    assert set(toks.tolist()) <= {0, 1}
    assert len(set(toks.tolist())) == 2  # still samples, not greedy


# ------------------------------------------------------ the pool container
def test_paged_kv_cache_container():
    """Pure device-array container (block accounting lives in the scheduler)."""
    kv = PagedKVCache(
        num_layers=2, num_blocks=8, block_size=4, num_kv_heads=2,
        head_dim=4, dtype='float32',
    )
    assert kv.shape == (2, 8, 4, 2, 4)  # the logical shape
    assert kv.k_pool.shape == kv.pool_shape == (2, 8, 4, 8)  # stored head-folded
    # the host's view: a layer, then block ids, in the logical shape
    assert len(kv.k) == 2 and kv.v[1][[3, 5]].shape == (2, 4, 2, 4)
    assert kv.blocks_needed(10) == 3
    assert kv.hbm_bytes == 2 * 2 * 8 * 4 * 2 * 4 * 4


@pytest.mark.parametrize('form', ['stacked', 'int8'])
@pytest.mark.parametrize('writer', ['token', 'chunk', 'prefill'])
def test_writers_fold_the_new_rows_and_the_host_view_unfolds_blocks(
    rng, writer, form
):
    """Each writer folds the NEW rows (``[.., N_kv, Hd]``) into the pool's
    ``N_kv * Hd`` rows, in the pages of the layer named, of the stacked
    pool handed whole; what the host's view gives back for a layer and
    block ids is the rows in their logical shape, for the float pool and
    the int8 container (a ``QuantizedKV`` of such blocks and their
    scales)."""
    from distllm_tpu.ops.paged_attention import QuantizedKV, write_chunk_kv

    kv = PagedKVCache(
        num_layers=2, num_blocks=6, block_size=4, num_kv_heads=2, head_dim=8,
        dtype='int8' if form == 'int8' else 'float32',
    )
    assert jax.tree.leaves(kv.k_pool)[0].shape[-2:] == (4, 16)  # folded
    rows = rng.normal(size=(8, 2, 8)).astype(np.float32)
    row = jnp.asarray([3, 5, 0, 0], jnp.int32)  # 8 tokens into blocks 3, 5
    k, v = kv.k_pool, kv.v_pool
    if writer == 'token':
        for t in range(8):
            k, v = write_token_kv(
                k, v, jnp.asarray(rows[t:t + 1]),
                jnp.asarray(2 * rows[t:t + 1]), row[None],
                jnp.asarray([t], jnp.int32), layer=1,
            )
    elif writer == 'chunk':
        for start in (0, 4):
            k, v = write_chunk_kv(
                k, v, jnp.asarray(rows[None, start:start + 4]),
                jnp.asarray(2 * rows[None, start:start + 4]), row[None],
                jnp.arange(start, start + 4)[None], jnp.ones((1, 4), bool),
                layer=1,
            )
    else:
        k, v = write_prefill_kv(
            k, v, jnp.asarray(rows), jnp.asarray(2 * rows), row,
            jnp.int32(8), layer=1,
        )
    kv.k_pool, kv.v_pool = k, v

    want = rows.reshape(2, 4, 2, 8)  # [blocks, block_size, N_kv, Hd]
    got_k, got_v = kv.k[1][[3, 5]], kv.v[1][[3, 5]]
    if form == 'int8':
        assert isinstance(got_k, QuantizedKV)
        assert got_k.data.shape == (2, 4, 2, 8) and got_k.scale.shape == (2, 2)
        for got, scaled in ((got_k, want), (got_v, 2 * want)):
            scale = np.asarray(got.scale)[:, None, :, None]
            deq = np.asarray(got.data, np.float32) * scale
            # an append re-rounds the rows before it: a step and a half
            assert (np.abs(deq - scaled) <= 1.5 * scale + 1e-6).all()
        untouched = np.asarray(kv.k[0][[3, 5]].data)
    else:
        assert got_k.shape == (2, 4, 2, 8)
        np.testing.assert_array_equal(np.asarray(got_k), want)
        np.testing.assert_array_equal(np.asarray(got_v), 2 * want)
        # block ids of any shape: [rows, 2] gives [rows, 2, block, N_kv, Hd]
        ends = kv.k[1][np.asarray([[3, 5], [5, 3]])]
        assert ends.shape == (2, 2, 4, 2, 8)
        np.testing.assert_array_equal(np.asarray(ends[1, 0]), want[1])
        untouched = np.asarray(kv.k[0][[3, 5]])
    assert not untouched.any()  # the other layer


def test_host_view_gathers_the_blocks_asked_for_and_no_plane():
    """``kv.k[layer][block_ids]`` is a gather of those blocks and a reshape
    of the gathered blocks: nothing it computes is the size of a layer's
    plane (the laguna cell's pools fill 91% of the device)."""
    from distllm_tpu.generate.engine.kv_cache import _PoolView

    kv = PagedKVCache(
        num_layers=3, num_blocks=64, block_size=4, num_kv_heads=2, head_dim=8,
        dtype='float32',
    )
    ids = np.asarray([[7, 9], [1, 63]])
    view = _PoolView(kv, kv.k_pool)
    jaxpr = jax.make_jaxpr(lambda pool: view._gather(pool, 2, ids))(kv.k_pool)
    asked = ids.size * 4 * 2 * 8
    sizes = [
        int(np.prod(var.aval.shape))
        for eqn in jaxpr.jaxpr.eqns for var in eqn.outvars
    ]
    assert sizes and max(sizes) <= asked < 64 * 4 * 2 * 8
    # ... which come back to the host and are unfolded there
    got = kv.k[2][ids]
    assert isinstance(got, np.ndarray) and got.shape == (2, 2, 4, 2, 8)
    with pytest.raises(IndexError):
        kv.k[3]
    with pytest.raises(AttributeError):
        kv.k = kv.k_pool  # the programs' operands are k_pool / v_pool


def _sliced_out_and_back(monkeypatch):
    """The path this family's programs took before the pool was addressed:
    a layer's plane sliced out of the stacked pool, written or read alone,
    and written back. A write through it cannot touch another layer."""
    from distllm_tpu.ops import paged_attention as pa

    def slice_of(cache, layer):
        if layer is None:  # a plane already: the dispatcher's inner call
            return cache
        return jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, layer, 0, False), cache
        )

    def writer(name):
        whole = getattr(pa, name)

        def sliced(k, v, *args, layer=None):
            k_l, v_l = whole(slice_of(k, layer), slice_of(v, layer), *args)
            return tuple(
                jax.tree.map(
                    lambda c, cl: jax.lax.dynamic_update_index_in_dim(
                        c, cl, layer, 0
                    ), cache, cache_l,
                ) for cache, cache_l in ((k, k_l), (v, v_l))
            )

        monkeypatch.setattr(pa, name, sliced)

    def reader(name):
        whole = getattr(pa, name)

        def sliced(q, k, v, *args, layer=None, **kwargs):
            return whole(
                q, slice_of(k, layer), slice_of(v, layer), *args, **kwargs
            )

        monkeypatch.setattr(pa, name, sliced)

    for name in ('write_token_kv', 'write_chunk_kv'):
        writer(name)
    for name in (
        'paged_attention_xla', 'ragged_paged_attention',
        'ragged_paged_attention_pallas',
    ):
        reader(name)


def test_engine_addresses_the_stacked_pool_by_layer(monkeypatch):
    """A greedy ``generate_ids`` over a 3-layer toy (prefix cache, chunked
    prefill and decode windows, so the span writer, the token writer and
    both readers all run on the stacked pool with a layer named): the
    tokens are the dense forward's and the sliced path's, and every byte
    of both pools is what the sliced path left, which can only write the
    layer it was handed: no write strays into a layer it did not name."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    shared = [int(t) for t in rng.integers(1, 64, size=9)]
    prompts = [
        shared + [int(t) for t in rng.integers(1, 64, size=n)]
        for n in (2, 12, 5)
    ]
    sampling = SamplingParams(temperature=0.0, max_tokens=7)

    def run():
        class IdTokenizer:
            eos_id = None

        engine = LLMEngine(
            cfg, params, IdTokenizer(),
            EngineConfig(
                block_size=4, num_blocks=48, max_num_seqs=3, max_model_len=64,
                decode_steps=4, pipeline_depth=1,
                attn_backend='interpret',  # the kernel's own wrapper
                enable_prefix_cache=True, prefill_chunk_tokens=8,
                prefer_native_allocator=False,
            ),
        )
        outs = engine.generate_ids(prompts, sampling)
        assert engine.kv.k_pool.shape == (3, 48, 4, 16)  # stacked, folded
        pools = np.asarray(engine.kv.k_pool), np.asarray(engine.kv.v_pool)
        engine.shutdown()
        return outs, pools

    outs, pools = run()
    assert outs == [_dense_greedy_reference(cfg, params, p, 7) for p in prompts]
    _sliced_out_and_back(monkeypatch)
    sliced_outs, sliced_pools = run()
    assert outs == sliced_outs
    for got, want in zip(pools, sliced_pools):
        assert got[:, 1:].any(axis=(1, 2, 3)).all()  # every layer was written
        # past each layer's trash block, where dead rows land in no order
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])


def test_sampling_windowed_matches_exact_when_cutoff_inside_window():
    """A peaky distribution's top-p cutoff falls inside the window, so the
    windowed fast path must keep the identical support; with the same key
    and identical filtered logits the sampled tokens agree exactly."""
    from distllm_tpu.ops.sampling import sample_tokens_windowed

    rng = np.random.default_rng(0)
    base = rng.normal(size=(32, 64)).astype(np.float32)
    base[:, :4] += 12.0  # concentrate ~all mass in 4 tokens
    logits = jnp.asarray(base)
    temp = jnp.full(32, 0.8)
    top_p = jnp.full(32, 0.9)
    min_p = jnp.zeros(32)
    # The window changes no threshold here, so the draws themselves agree
    # (one categorical over the vocabulary either way); compare supports
    # over many keys as well.
    exact_set, win_set = set(), set()
    for i in range(40):
        k = jax.random.PRNGKey(i)
        exact_set.update(
            np.asarray(sample_tokens(logits, k, temp, top_p, min_p)).tolist()
        )
        win_set.update(
            np.asarray(
                sample_tokens_windowed(logits, k, temp, top_p, min_p, 8)
            ).tolist()
        )
    assert exact_set == win_set
    assert exact_set <= set(range(4))


def test_sampling_windowed_truncates_flat_distribution_to_window():
    from distllm_tpu.ops.sampling import sample_tokens_windowed

    # Nearly uniform, no two logits equal: top-p needs ~all tokens, the
    # window caps the support at the 16 largest.
    logits = jnp.tile(jnp.arange(128.0)[None, :] * 1e-3, (64, 1))
    toks = np.asarray(
        sample_tokens_windowed(
            logits, jax.random.PRNGKey(0), jnp.ones(64),
            jnp.full(64, 0.99), jnp.zeros(64), 16,
        )
    )
    assert set(toks.tolist()) <= set(range(112, 128))
    assert len(set(toks.tolist())) > 8  # still samples across the window
    # Tokens tied with the window's smallest value all stay (vLLM's rule:
    # mask what is under the k-th value), so a flat row keeps its support.
    flat = np.asarray(
        sample_tokens_windowed(
            jnp.zeros((64, 128)), jax.random.PRNGKey(0), jnp.ones(64),
            jnp.full(64, 0.99), jnp.zeros(64), 16,
        )
    )
    assert len(set(flat.tolist())) > 16


def test_sampling_windowed_greedy_and_engine_path():
    from distllm_tpu.ops.sampling import sample_tokens_windowed

    logits = jnp.asarray([[0.0, 5.0, 1.0, -1.0], [3.0, 0.0, 0.1, 2.0]])
    toks = sample_tokens_windowed(
        logits, jax.random.PRNGKey(0), jnp.zeros(2), jnp.ones(2),
        jnp.zeros(2), 2,
    )
    assert list(np.asarray(toks)) == [1, 0]
    # top_window >= V must dispatch to the exact path unchanged.
    toks2 = sample_tokens(
        logits, jax.random.PRNGKey(0), jnp.zeros(2), jnp.ones(2),
        jnp.zeros(2), top_window=99,
    )
    assert list(np.asarray(toks2)) == [1, 0]
