"""Generation engine tests: paged attention, sampling, allocator, engine vs
dense-forward golden decoding."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.generate.engine import (
    EngineConfig,
    LLMEngine,
    RequestState,
    SamplingParams,
)
from distllm_tpu.generate.engine.kv_cache import (
    NativeBlockAllocator,
    PagedKVCache,
    PyBlockAllocator,
)
from distllm_tpu.models import mistral
from distllm_tpu.ops.paged_attention import (
    paged_attention_xla,
    write_prefill_kv,
    write_token_kv,
)
from distllm_tpu.ops.sampling import sample_tokens


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


# -------------------------------------------------------------- allocator
@pytest.mark.parametrize('cls', [PyBlockAllocator, NativeBlockAllocator])
def test_block_allocator(cls):
    try:
        alloc = cls(8)
    except RuntimeError:
        pytest.skip('native toolchain unavailable')
    assert alloc.num_free == 7  # block 0 reserved
    blocks = [alloc.alloc() for _ in range(7)]
    assert 0 not in blocks
    assert alloc.alloc() == -1  # exhausted
    alloc.incref(blocks[0])
    alloc.free(blocks[0])
    assert alloc.num_free == 0  # still referenced
    alloc.free(blocks[0])
    assert alloc.num_free == 1
    with pytest.raises((AssertionError, ValueError)):
        alloc.free(blocks[0])  # double free


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


def _layer_of(pool, layer, layer_buffers):
    if layer_buffers:
        return pool[layer]
    return jax.tree.map(lambda c: c[layer], pool)


def _with_layer(pool, layer, buf, layer_buffers):
    if layer_buffers:
        return tuple(buf if i == layer else b for i, b in enumerate(pool))
    return jax.tree.map(lambda c, b: c.at[layer].set(b), pool, buf)


@pytest.mark.parametrize('form', ['stacked', 'layer_buffers', 'int8'])
@pytest.mark.parametrize('writer', ['token', 'chunk', 'prefill'])
def test_writers_fold_the_new_rows_and_the_host_view_unfolds_blocks(
    rng, writer, form
):
    """Each writer folds the NEW rows (``[.., N_kv, Hd]``) into the pool's
    ``N_kv * Hd`` rows; what the host's view gives back for a layer and
    block ids is the rows in their logical shape, for both pool forms and
    the int8 container (a ``QuantizedKV`` of such blocks and their
    scales)."""
    from distllm_tpu.ops.paged_attention import QuantizedKV, write_chunk_kv

    layer_buffers = form == 'layer_buffers'
    kv = PagedKVCache(
        num_layers=2, num_blocks=6, block_size=4, num_kv_heads=2, head_dim=8,
        dtype='int8' if form == 'int8' else 'float32',
        layer_buffers=layer_buffers,
    )
    assert jax.tree.leaves(kv.k_pool)[0].shape[-2:] == (4, 16)  # folded
    rows = rng.normal(size=(8, 2, 8)).astype(np.float32)
    row = jnp.asarray([3, 5, 0, 0], jnp.int32)  # 8 tokens into blocks 3, 5
    k_l = _layer_of(kv.k_pool, 1, layer_buffers)
    v_l = _layer_of(kv.v_pool, 1, layer_buffers)
    if writer == 'token':
        for t in range(8):
            k_l, v_l = write_token_kv(
                k_l, v_l, jnp.asarray(rows[t:t + 1]),
                jnp.asarray(2 * rows[t:t + 1]), row[None],
                jnp.asarray([t], jnp.int32),
            )
    elif writer == 'chunk':
        for start in (0, 4):
            k_l, v_l = write_chunk_kv(
                k_l, v_l, jnp.asarray(rows[None, start:start + 4]),
                jnp.asarray(2 * rows[None, start:start + 4]), row[None],
                jnp.arange(start, start + 4)[None], jnp.ones((1, 4), bool),
            )
    else:
        k_l, v_l = write_prefill_kv(
            k_l, v_l, jnp.asarray(rows), jnp.asarray(2 * rows), row,
            jnp.int32(8),
        )
    kv.k_pool = _with_layer(kv.k_pool, 1, k_l, layer_buffers)
    kv.v_pool = _with_layer(kv.v_pool, 1, v_l, layer_buffers)

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


@pytest.mark.parametrize('layer_buffers', [False, True], ids=['stacked', 'layer_buffers'])
def test_host_view_gathers_the_blocks_asked_for_and_no_buffer(layer_buffers):
    """``kv.k[layer][block_ids]`` is a gather of those blocks and a reshape
    of the gathered blocks: nothing it computes is the size of a layer's
    buffer (the laguna cell's pools fill 91% of the device)."""
    from distllm_tpu.generate.engine.kv_cache import _PoolView

    kv = PagedKVCache(
        num_layers=3, num_blocks=64, block_size=4, num_kv_heads=2, head_dim=8,
        dtype='float32', layer_buffers=layer_buffers,
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


# ----------------------------------------------------------------- engine
def _tiny_engine(num_blocks=64, max_num_seqs=4, max_model_len=64, **cfg_kwargs):
    cfg = mistral.MistralConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=64,
        dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class IdTokenizer:
        eos_id = None

        def decode(self, ids):
            return ' '.join(str(i) for i in ids)

    engine = LLMEngine(
        cfg,
        params,
        IdTokenizer(),
        EngineConfig(
            block_size=4,
            num_blocks=num_blocks,
            max_num_seqs=max_num_seqs,
            max_model_len=max_model_len,
            prefer_native_allocator=False,
            **cfg_kwargs,
        ),
    )
    return cfg, params, engine


def _dense_greedy_reference(cfg, params, prompt, n_tokens):
    """Greedy decoding via full dense re-forward each step (gold path)."""
    ids = list(prompt)
    for _ in range(n_tokens):
        arr = np.asarray([ids], np.int32)
        mask = np.ones_like(arr)
        hidden = mistral.apply(params, cfg, arr, mask)
        lg = mistral.logits(params, cfg, hidden[:, -1])
        ids.append(int(np.argmax(np.asarray(lg)[0])))
    return ids[len(prompt):]


def test_engine_greedy_matches_dense_forward():
    cfg, params, engine = _tiny_engine()
    prompts = [[5, 9, 12], [7, 3, 22, 31, 40, 2, 17], [1, 2, 3, 4, 5]]
    n = 8
    params_greedy = SamplingParams(temperature=0.0, max_tokens=n)
    outs = engine.generate_ids(prompts, params_greedy)
    for prompt, out in zip(prompts, outs):
        ref = _dense_greedy_reference(cfg, params, prompt, n)
        assert out == ref, f'{out} != {ref}'


def test_engine_batched_prefill_matches_dense_forward():
    """Many same-bucket prompts prefill in one padded dispatch; tokens must
    match the dense greedy reference exactly (padding rows are discarded,
    their K/V lands in the trash block)."""
    cfg, params, engine = _tiny_engine(num_blocks=128, max_num_seqs=8)
    rng = np.random.default_rng(3)
    # 6 prompts in the same 8-bucket + 3 in the 16-bucket: exercises a
    # full-8 pad, a partial pad, and cross-bucket grouping in one _admit.
    prompts = [list(rng.integers(1, 64, size=6)) for _ in range(6)]
    prompts += [list(rng.integers(1, 64, size=12)) for _ in range(3)]
    assert engine._prefill_batch_cap(8) >= 4
    outs = engine.generate_ids(prompts, SamplingParams(temperature=0.0, max_tokens=5))
    for prompt, out in zip(prompts, outs):
        assert out == _dense_greedy_reference(cfg, params, prompt, 5)


def test_engine_warmup_compiles_without_state_damage():
    """warmup() must not disturb scheduler state, the sampling RNG stream,
    or later generations."""
    cfg, params, engine = _tiny_engine()
    engine.warmup()
    assert engine.sched.num_running == 0
    assert engine.sched.num_free_blocks == 63  # all but trash block 0
    prompts = [[5, 9, 12], [7, 3, 22, 31]]
    outs = engine.generate_ids(prompts, SamplingParams(temperature=0.0, max_tokens=4))
    for prompt, out in zip(prompts, outs):
        assert out == _dense_greedy_reference(cfg, params, prompt, 4)
    # Seeded stochastic sampling reproduces between warmed/unwarmed engines
    # (keys are counter-derived per request, so warmup cannot advance any
    # sampling stream — docs/speculative.md "Sampled verification").
    _, _, warmed = _tiny_engine()
    warmed.warmup()
    _, _, fresh = _tiny_engine()
    sp = SamplingParams(temperature=0.9, max_tokens=6)
    assert warmed.generate_ids([[4, 2]], sp) == fresh.generate_ids([[4, 2]], sp)


def test_prefill_batch_cap_bounded_by_max_num_seqs():
    cfg, params, engine = _tiny_engine(max_num_seqs=3)
    engine.config.max_prefill_batch = 8
    # groups can never exceed 3 running slots -> pads to at most 4
    assert engine._prefill_batch_cap(8) == 4


def test_prefill_batch_cap_honors_token_budget():
    cfg, params, engine = _tiny_engine(max_num_seqs=8)
    engine.config.max_prefill_tokens = 64
    engine.config.max_prefill_batch = 8
    assert engine._prefill_batch_cap(8) == 8
    assert engine._prefill_batch_cap(16) == 4
    assert engine._prefill_batch_cap(64) == 1
    assert engine._prefill_batch_cap(128) == 1


def test_engine_continuous_batching_join_leave():
    """Requests with different lengths join/leave the batch mid-flight."""
    cfg, params, engine = _tiny_engine(max_num_seqs=2)
    sp_short = SamplingParams(temperature=0.0, max_tokens=2)
    sp_long = SamplingParams(temperature=0.0, max_tokens=6)
    r1 = engine.add_request([5, 6, 7], sp_long)
    r2 = engine.add_request([9, 8], sp_short)
    r3 = engine.add_request([11, 12, 13], sp_short)  # waits for a slot
    seen = {}
    while engine.has_unfinished:
        for rid, tok in engine.step():
            seen.setdefault(rid, []).append(tok)
    assert len(seen[r1]) == 6
    assert len(seen[r2]) == 2
    assert len(seen[r3]) == 2
    # all finished requests got their outputs recorded & slots/blocks freed
    assert engine.sched.num_running == 0
    ref = _dense_greedy_reference(cfg, params, [5, 6, 7], 6)
    assert seen[r1] == ref


def _expect_short_answers(engine):
    """As if finished requests had used none of their budgets: admission's
    look-ahead (scheduler.py, "Admission by decode budget") then sees one
    window ahead, as the admission rule before it saw one token, so a
    small pool runs short under rows that run to ``max_tokens`` and
    recompute preemption, the net under a low estimate, has to catch it.
    Returns a callable that says how many victims there were since."""
    from distllm_tpu.observability import instruments

    engine._ewma['budget_use'] = 0.0
    before = instruments.SCHED_PREEMPTIONS.value
    return lambda: instruments.SCHED_PREEMPTIONS.value - before


def test_engine_preemption_under_block_pressure():
    """Tiny block pool forces recompute preemption; outputs still correct
    and complete (no tokens lost across preemption)."""
    # 7 usable blocks, 3 seqs needing 3 blocks each -> guaranteed pressure.
    cfg, params, engine = _tiny_engine(
        num_blocks=8, max_num_seqs=3, max_model_len=32, decode_steps=2
    )
    victims = _expect_short_answers(engine)
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    prompts = [[5, 9, 12, 4], [7, 3, 22, 31], [1, 2, 3, 4]]
    outs = engine.generate_ids(prompts, sp)
    assert victims() > 0
    for prompt, out in zip(prompts, outs):
        ref = _dense_greedy_reference(cfg, params, prompt, 6)
        assert out == ref
    # No block leaks: everything freed at the end.
    assert engine.sched.num_free_blocks == 7


def test_engine_prompt_at_max_model_len():
    """A prompt >= max_model_len truncates (keeping the tail) and still runs."""
    cfg, params, engine = _tiny_engine(num_blocks=64, max_model_len=16)
    sp = SamplingParams(temperature=0.0, max_tokens=2)
    prompt = list(range(1, 41))  # 40 tokens, max_model_len 16
    out = engine.generate_ids([prompt], sp)[0]
    ref = _dense_greedy_reference(cfg, params, prompt[-15:], 1)
    assert out[0] == ref[0]


def test_engine_unadmittable_prompt_raises():
    cfg, params, engine = _tiny_engine(num_blocks=4, max_model_len=32)
    with pytest.raises(ValueError, match='KV blocks'):
        engine.add_request(list(range(1, 30)))


def test_decode_sliding_window_matches_dense():
    """Sliding-window decode must equal dense forward with the window mask."""
    cfg = mistral.MistralConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=64,
        sliding_window=4,
        dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(3), cfg)

    class IdTok:
        eos_id = None

        def decode(self, ids):
            return ''

    engine = LLMEngine(
        cfg, params, IdTok(),
        EngineConfig(
            block_size=4, num_blocks=32, max_num_seqs=2, max_model_len=32,
            prefer_native_allocator=False,
        ),
    )
    prompt = [5, 9, 12, 4, 7, 3]
    out = engine.generate_ids([prompt], SamplingParams(temperature=0.0, max_tokens=5))[0]
    ref = _dense_greedy_reference(cfg, params, prompt, 5)
    assert out == ref


def test_engine_stop_tokens():
    cfg, params, engine = _tiny_engine()
    ref = _dense_greedy_reference(cfg, params, [5, 9, 12], 8)
    stop = ref[3]
    sp = SamplingParams(temperature=0.0, max_tokens=20, stop_token_ids=(stop,))
    out = engine.generate_ids([[5, 9, 12]], sp)[0]
    assert out == ref[: ref.index(stop)]  # truncated at stop, token stripped


def test_engine_quantized_weights_generate():
    """Weight-only int8 serving (EngineConfig.quantization) runs the full
    prefill+decode path and mostly agrees with full-precision greedy."""
    cfg = mistral.MistralConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=64,
        dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class IdTokenizer:
        eos_id = None

        def decode(self, ids):
            return ' '.join(str(i) for i in ids)

    engine = LLMEngine(
        cfg,
        params,
        IdTokenizer(),
        EngineConfig(
            block_size=4,
            num_blocks=64,
            max_num_seqs=4,
            max_model_len=64,
            prefer_native_allocator=False,
            quantization='int8',
        ),
    )
    outs = engine.generate_ids(
        [[5, 9, 12]], SamplingParams(temperature=0.0, max_tokens=6)
    )
    assert len(outs[0]) == 6
    assert all(0 <= t < 64 for t in outs[0])


def test_engine_decode_steps_variants_match_dense():
    """K=1 (legacy per-token), K=4, and deep pipelining must all produce
    the dense greedy reference exactly — EOS overshoot tokens are
    discarded and budgets respected regardless of window shape."""
    prompts = [[5, 9, 12], [7, 3, 22, 31, 40, 2, 17]]
    n = 7  # deliberately not a multiple of any window size
    ref_cfg, ref_params, ref_engine = _tiny_engine()
    refs = [
        _dense_greedy_reference(ref_cfg, ref_params, p, n) for p in prompts
    ]
    for steps, depth in ((1, 1), (4, 1), (4, 3), (8, 2)):
        cfg = mistral.MistralConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, intermediate_size=64, dtype='float32',
        )
        params = mistral.init(jax.random.PRNGKey(0), cfg)

        class IdTokenizer:
            eos_id = None

        engine = LLMEngine(
            cfg, params, IdTokenizer(),
            EngineConfig(
                block_size=4, num_blocks=64, max_num_seqs=4,
                max_model_len=64, prefer_native_allocator=False,
                decode_steps=steps, pipeline_depth=depth,
            ),
        )
        outs = engine.generate_ids(
            prompts, SamplingParams(temperature=0.0, max_tokens=n)
        )
        assert outs == refs, f'steps={steps} depth={depth}: {outs} != {refs}'


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


def test_engine_pipelined_preemption_pressure_matches_dense():
    """A pool too small for all sequences forces recompute preemption mid-
    pipeline; the drain-before-preempt rule must keep results exact."""
    cfg, params, engine = _tiny_engine(
        num_blocks=10, max_num_seqs=3, decode_steps=2
    )
    victims = _expect_short_answers(engine)
    prompts = [[5, 9, 12], [7, 3, 22, 31], [1, 2, 3, 4, 5]]
    n = 12
    outs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=n)
    )
    assert victims() > 0
    for prompt, out in zip(prompts, outs):
        assert out == _dense_greedy_reference(cfg, params, prompt, n)


def test_engine_max_tokens_below_window():
    """max_tokens=1 with decode_steps=8: the prefill emits the only token
    and the window machinery must not emit more."""
    cfg, params, engine = _tiny_engine()
    outs = engine.generate_ids(
        [[5, 9, 12]], SamplingParams(temperature=0.0, max_tokens=1)
    )
    assert len(outs[0]) == 1
    assert outs[0] == _dense_greedy_reference(cfg, params, [5, 9, 12], 1)


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


def test_engine_greedy_gemma2_matches_dense_forward():
    """The paged decode path (traced per-layer windows, softcaps, sandwich
    norms, (1+w) norms, scaled embeddings) serves gemma2 token-exactly vs
    the dense re-forward — long enough that decode positions pass the
    sliding window on the local (even) layers."""
    from distllm_tpu.models import gemma

    cfg = gemma.GemmaConfig(
        name='gemma2', vocab_size=64, hidden_size=32, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=64,
        max_position_embeddings=64, dtype='float32',
        activation='gelu_new', embedding_multiplier=32 ** 0.5,
        norm_plus_one=True, post_norms=True, query_scale=16 ** -0.5,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        sliding_window=6, sliding_window_pattern='alternating',
        tie_word_embeddings=True, rms_norm_eps=1e-6,
    )
    params = gemma.init(jax.random.PRNGKey(1), cfg)

    class IdTokenizer:
        eos_id = None

        def decode(self, ids):
            return ' '.join(str(i) for i in ids)

    engine = LLMEngine(
        cfg, params, IdTokenizer(),
        EngineConfig(
            block_size=4, num_blocks=64, max_num_seqs=4, max_model_len=64,
            prefer_native_allocator=False,
        ),
    )
    prompts = [[5, 9, 12], [7, 3, 22, 31, 40, 2, 17]]
    n = 10  # prompt+decode crosses the window=6 boundary
    outs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=n)
    )

    def dense_greedy(prompt):
        ids = list(prompt)
        for _ in range(n):
            arr = np.asarray([ids], np.int32)
            hidden = gemma.apply(params, cfg, arr, np.ones_like(arr))
            lg = gemma.logits(params, cfg, hidden[:, -1])
            ids.append(int(np.argmax(np.asarray(lg)[0])))
        return ids[len(prompt):]

    for prompt, out in zip(prompts, outs):
        ref = dense_greedy(prompt)
        assert out == ref, f'{out} != {ref}'


def test_engine_deferred_prefill_matches_dense_forward():
    # Opt-in pipelined prefill emission (EngineConfig.defer_prefill):
    # first tokens stay on device, scatter into the carried last-ids
    # vector, and are fetched one window late. Must stay token-exact vs
    # the dense reference, including continuous-batching slot reuse
    # (more prompts than slots) and a mid-stream finisher.
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class IdTokenizer:
        eos_id = None

    engine = LLMEngine(
        cfg, params, IdTokenizer(),
        EngineConfig(
            block_size=4, num_blocks=64, max_num_seqs=2, max_model_len=64,
            decode_steps=4, pipeline_depth=2, defer_prefill=True,
            prefer_native_allocator=False,
        ),
    )
    prompts = [[5, 9, 12], [7, 3, 22, 31, 40, 2, 17], [1, 2, 3, 4, 5],
               [44, 13], [9], [30, 31, 32, 33]]
    lens = [6, 9, 1, 8, 5, 7]  # mixed budgets incl. max_tokens=1
    rids = [
        engine.add_request(p, SamplingParams(temperature=0.0, max_tokens=n))
        for p, n in zip(prompts, lens)
    ]
    engine._run_to_completion()
    for p, n, rid in zip(prompts, lens, rids):
        got = engine._finished.pop(rid).output_ids
        ref = _dense_greedy_reference(cfg, params, p, n)
        assert got == ref, f'{got} != {ref}'


# ----------------------------------------- mixed prefill+decode windows
def test_ragged_paged_attention_decode_rows_match_decode_kernel(rng):
    """A ragged row with q_len=1 at position ctx-1 IS a decode row: the
    ragged path must agree with paged_attention_xla, with multi-token
    chunk rows coexisting in the same ragged batch."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention_xla

    block_size = 4
    k_cache, v_cache = _random_cache(rng, block_size=block_size)
    block_tables = jnp.asarray([[2, 5], [7, 3]], dtype=jnp.int32)
    context_lens = jnp.asarray([6, 5], dtype=jnp.int32)
    s = 3
    q = jnp.asarray(rng.normal(size=(2, s, 4, 8)).astype(np.float32))
    # Row 0: decode row — one valid query at its last position. Row 1: a
    # causal 3-token chunk span ending at position 4.
    q_positions = jnp.asarray([[5, 5, 5], [2, 3, 4]], dtype=jnp.int32)
    q_lens = jnp.asarray([1, 3], dtype=jnp.int32)
    out = np.asarray(
        ragged_paged_attention_xla(
            q, k_cache, v_cache, block_tables, context_lens, q_positions,
            q_lens=q_lens,
        )
    )
    dec = np.asarray(
        paged_attention_xla(
            q[:, 0], k_cache, v_cache, block_tables, context_lens
        )
    )
    np.testing.assert_allclose(out[0, 0], dec[0], atol=1e-5, rtol=1e-5)
    # Chunk row: each query vs a dense causal reference over its prefix.
    for j, pos in enumerate([2, 3, 4]):
        k_lin = np.concatenate(
            [_heads(k_cache[7]), _heads(k_cache[3])]
        )
        v_lin = np.concatenate(
            [_heads(v_cache[7]), _heads(v_cache[3])]
        )
        ref = _dense_reference(np.asarray(q[1, j]), k_lin, v_lin, pos + 1)
        np.testing.assert_allclose(out[1, j], ref, atol=1e-5, rtol=1e-4)
    # Padding queries (masked by q_lens) must stay finite.
    assert np.isfinite(out).all()


def _mixed_ab_engines(model_cfg, init_fn, seed=0, **cfg_kw):
    """Build (off, on) engines with identical weights for A/B runs."""
    class IdTokenizer:
        eos_id = None

    engines = []
    for mixed in (False, True):
        base = dict(
            block_size=4, num_blocks=96, max_num_seqs=2, max_model_len=96,
            decode_steps=4, pipeline_depth=2,
            prefer_native_allocator=False, enable_mixed_batching=mixed,
            max_window_prefill_tokens=8, max_window_prefill_seqs=2,
        )
        base.update(cfg_kw)
        engines.append(
            LLMEngine(
                model_cfg,
                init_fn(jax.random.PRNGKey(seed), model_cfg),
                IdTokenizer(),
                EngineConfig(**base),
            )
        )
    return engines


_STAGGER_PROMPT_LENS = (5, 21, 3, 33, 7, 13)
_STAGGER_OUT_LENS = (3, 17, 9, 5, 12, 8)


def _stagger_prompts(vocab, seed=1):
    """Staggered serving workload: more prompts than slots, unequal
    budgets (slots free mid-stream — the mixed-batching trigger), two
    prompts sharing a 2-block prefix (prefix-cache-hit tails ride), and
    long prompts whose tails chunk (chunk spans ride)."""
    rng = np.random.default_rng(seed)
    prompts = [
        list(rng.integers(1, vocab, size=n)) for n in _STAGGER_PROMPT_LENS
    ]
    shared = list(rng.integers(1, vocab, size=8))  # 2 full 4-blocks
    prompts[0] = shared + prompts[0]
    prompts[4] = shared + prompts[4]
    return prompts


def _run_stagger(engine, vocab, seed=1):
    prompts = _stagger_prompts(vocab, seed)
    rids = [
        engine.add_request(
            p, SamplingParams(temperature=0.0, max_tokens=n)
        )
        for p, n in zip(prompts, _STAGGER_OUT_LENS)
    ]
    engine._run_to_completion()
    return [engine._finished.pop(r).output_ids for r in rids]


@pytest.mark.slow
@pytest.mark.parametrize(
    'cache_kw',
    [
        {'enable_prefix_cache': True},
        {'enable_prefix_cache': True, 'prefill_chunk_tokens': 4},
        {'prefill_chunk_tokens': 4},
    ],
    ids=['prefix_cache', 'prefix_cache_chunked', 'chunked'],
)
def test_mixed_windows_token_identity(cache_kw):
    """Mixed on/off must emit bit-identical greedy tokens across prefix
    cache on/off and chunked tails, under pipelined (pipeline_depth=2)
    dispatch with mid-stream admissions — and wherever paged-route tails
    exist, the on run must actually fold them into windows (mixed
    records, fewer standalone dispatches). Only paged-route tails ride
    (cache-hit tails / chunked spans): fresh short prompts keep the
    batched dense prefill in BOTH arms, which is what makes identity a
    structural property rather than a cross-kernel numerics gamble."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    off, on = _mixed_ab_engines(cfg, mistral.init, **cache_kw)
    assert _run_stagger(on, cfg.vocab_size) == _run_stagger(
        off, cfg.vocab_size
    )
    if cache_kw.get('enable_prefix_cache'):
        # Second pass over the same workload: pass 1 populated the prefix
        # cache, so these shared-prefix repeats are CACHE-HIT admissions —
        # the cached-tail ride path a single cold batch can never reach
        # (all add_requests land before anything prefills).
        assert _run_stagger(on, cfg.vocab_size) == _run_stagger(
            off, cfg.vocab_size
        )
    assert on._stats['mixed_windows'] > 0
    assert on._stats['mixed_prefill_tokens'] > 0
    assert (
        on._stats['prefill_dispatches'] < off._stats['prefill_dispatches']
    )


@pytest.mark.slow
def test_mixed_windows_token_identity_sliding_window():
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, sliding_window=4,
        dtype='float32',
    )
    off, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=4
    )
    outs_off = _run_stagger(off, cfg.vocab_size)
    outs_on = _run_stagger(on, cfg.vocab_size)
    assert outs_on == outs_off
    assert on._stats['mixed_windows'] > 0


@pytest.mark.slow
def test_mixed_windows_token_identity_gemma2():
    """gemma2-style serving (alternating windows, softcaps, sandwich
    norms, query_scale) through mixed windows stays token-exact."""
    from distllm_tpu.models import gemma

    cfg = gemma.GemmaConfig(
        name='gemma2', vocab_size=64, hidden_size=32, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=64,
        max_position_embeddings=128, dtype='float32',
        activation='gelu_new', embedding_multiplier=32 ** 0.5,
        norm_plus_one=True, post_norms=True, query_scale=16 ** -0.5,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        sliding_window=6, sliding_window_pattern='alternating',
        tie_word_embeddings=True, rms_norm_eps=1e-6,
    )
    off, on = _mixed_ab_engines(
        cfg, gemma.init, seed=1, prefill_chunk_tokens=4
    )
    outs_off = _run_stagger(off, cfg.vocab_size)
    outs_on = _run_stagger(on, cfg.vocab_size)
    assert outs_on == outs_off
    assert on._stats['mixed_windows'] > 0


@pytest.mark.slow
def test_mixed_windows_match_dense_reference_and_preemption():
    """Mixed serving equals the dense greedy gold path even when a tiny
    pool forces recompute preemption of mid-prefill rows."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, num_blocks=20, max_num_seqs=3, max_model_len=64,
        prefill_chunk_tokens=4, decode_steps=2,
    )
    victims = _expect_short_answers(on)
    outs = _run_stagger(on, cfg.vocab_size)
    assert victims() > 0
    prompts = _stagger_prompts(cfg.vocab_size)
    # Dense gold references for the two longest-prompt requests (the ones
    # whose chunk rides + preemption interact); the full-matrix identity
    # tests above cover the rest without the dense re-forward cost.
    for i in (1, 3):
        ref = _dense_greedy_reference(
            cfg, on.params, prompts[i], _STAGGER_OUT_LENS[i]
        )
        assert outs[i] == ref
    assert all(
        len(o) == n for o, n in zip(outs, _STAGGER_OUT_LENS)
    )
    assert on.sched.num_free_blocks == 19  # no block leaks


@pytest.mark.slow
def test_mixed_windows_step_api_mid_stream_admission():
    """The synchronous step() path plans and processes mixed windows too;
    a request injected mid-decode rides them and its TTFT lifecycle
    fields are recorded."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=2
    )
    # Budgets staggered so r1's slot frees while r2 still decodes: the
    # injected r3 is then admitted MID-STREAM (equal budgets drain both
    # slots in the same window and the admission would land on an idle
    # engine, which bootstraps standalone by design).
    prompts = [[5, 9, 12], [7, 3, 22, 31], [1, 2, 3, 4, 5]]
    budgets = [3, 14, 8]
    r1 = on.add_request(
        prompts[0], SamplingParams(temperature=0.0, max_tokens=budgets[0])
    )
    r2 = on.add_request(
        prompts[1], SamplingParams(temperature=0.0, max_tokens=budgets[1])
    )
    seen: dict[int, list[int]] = {}
    r3 = None
    while on.has_unfinished:
        for rid, tok in on.step():
            seen.setdefault(rid, []).append(tok)
        if r3 is None and len(seen.get(r1, [])) >= budgets[0]:
            r3 = on.add_request(
                prompts[2],
                SamplingParams(temperature=0.0, max_tokens=budgets[2]),
            )
    for prompt, n, rid in zip(prompts, budgets, (r1, r2, r3)):
        assert seen[rid] == _dense_greedy_reference(
            cfg, on.params, prompt, n
        )
    assert on._stats['mixed_windows'] > 0
    assert on._finished[r3].t_first_token > 0.0


@pytest.mark.slow
def test_mixed_windows_warmup_compiles_without_state_damage():
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=4, max_model_len=32,
    )
    on.warmup()
    assert on.sched.num_running == 0
    assert on.sched.num_free_blocks == 95
    # Short post-warmup serve must still match the dense gold path
    # (scheduler state was untouched by warmup; sampling keys are
    # counter-derived per request, so there is no RNG state to damage).
    prompts = [[5, 9, 12], [7, 3, 22, 31, 40, 2, 17]]
    outs = on.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=4)
    )
    for prompt, out in zip(prompts, outs):
        assert out == _dense_greedy_reference(cfg, on.params, prompt, 4)


def test_mixed_config_validation():
    with pytest.raises(ValueError, match='mutually exclusive'):
        EngineConfig(
            enable_mixed_batching=True, defer_prefill=True,
            prefill_chunk_tokens=16,
        )
    with pytest.raises(ValueError, match='max_window_prefill_tokens'):
        EngineConfig(
            enable_mixed_batching=True, max_window_prefill_tokens=0,
            prefill_chunk_tokens=16,
        )
    # Structurally inert combination: neither prefix cache nor chunking
    # means nothing can ever ride, yet warmup would compile the whole
    # mixed shape ladder — rejected at config time.
    with pytest.raises(ValueError, match='prefill_chunk_tokens'):
        EngineConfig(enable_mixed_batching=True)
    with pytest.raises(ValueError, match='>= 1'):
        EngineConfig(max_window_prefill_seqs=0)
    # defer_prefill alone stays a legal opt-in.
    assert EngineConfig(defer_prefill=True).defer_prefill


def test_mixed_windows_token_identity_fast_canary():
    """Fast-tier mixed on/off identity canary: chunked + prefix-cache
    config, staggered budgets, pipelined dispatch. The full matrix
    (cache on/off, sliding-window, gemma2, preemption, step API, warmup)
    runs in the slow tier — this keeps one end-to-end identity + fold
    assertion inside the 870 s tier-1 budget."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    off, on = _mixed_ab_engines(
        cfg, mistral.init, enable_prefix_cache=True,
        prefill_chunk_tokens=4,
    )
    prompts = _stagger_prompts(cfg.vocab_size)
    budgets = (2, 9, 4, 3, 6, 4)

    def run(engine):
        rids = [
            engine.add_request(
                p, SamplingParams(temperature=0.0, max_tokens=n)
            )
            for p, n in zip(prompts, budgets)
        ]
        engine._run_to_completion()
        return [engine._finished.pop(r).output_ids for r in rids]

    assert run(on) == run(off)
    assert on._stats['mixed_windows'] > 0
    assert (
        on._stats['prefill_dispatches'] < off._stats['prefill_dispatches']
    )


def test_mixed_flight_records_and_metrics():
    """Chunk-carrying windows record kind='mixed' with prefill payload
    fields, and the distllm_engine_mixed_* series advance."""
    from distllm_tpu.observability import instruments as metrics
    from distllm_tpu.observability.flight import get_flight_recorder

    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=4
    )
    before = len(
        [r for r in get_flight_recorder().snapshot() if r['kind'] == 'mixed']
    )
    windows_before = metrics.MIXED_WINDOWS.value
    tokens_before = metrics.MIXED_PREFILL_TOKENS.value
    _run_stagger(on, cfg.vocab_size)
    mixed_records = [
        r for r in get_flight_recorder().snapshot() if r['kind'] == 'mixed'
    ]
    assert len(mixed_records) > before
    rec = mixed_records[-1]
    assert rec['prefill_tokens'] > 0
    assert rec['prefill_rows'] >= 1
    assert metrics.MIXED_WINDOWS.value > windows_before
    assert metrics.MIXED_PREFILL_TOKENS.value > tokens_before


def test_mixed_exception_recovery_rolls_back_inflight_chunk_spans(
    monkeypatch,
):
    """A chunk span whose window is lost to an exception mid-drain must
    roll ``prefill_sent`` back to ``prefill_done`` so the span re-rides
    after a catch-and-continue resume — otherwise the planner skips the
    request as 'in flight' forever and the serving loop livelocks."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(cfg, mistral.init, prefill_chunk_tokens=2)
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    # Bootstrap one decoding request so the second one's tail rides.
    r1 = on.add_request([5, 9, 12], sp)
    while not on._requests[r1].output_ids:
        on.step()
    r2 = on.add_request([7, 3, 22, 31, 40], sp)

    armed = {'on': True}
    orig = LLMEngine._process_window

    def boom(self, window):
        if armed['on'] and window.get('chunk_plan'):
            armed['on'] = False  # lose exactly one chunk-carrying window
            raise RuntimeError('injected mid-drain')
        return orig(self, window)

    monkeypatch.setattr(LLMEngine, '_process_window', boom)
    with pytest.raises(RuntimeError, match='injected'):
        on._run_to_completion()
    req2 = on._requests[r2]
    assert req2.state is RequestState.RUNNING
    assert req2.prefill_sent == req2.prefill_done  # rolled back
    # The planner re-plans the dropped span instead of skipping it.
    assert any(
        request.request_id == r2
        for request, _, _ in on._plan_window_chunks()
    )


# ------------------------------------------------- the row walk's counter
def _walked_chunks(contexts, keys, window=None, block=4):
    """Chunks a row walk fetches for rows at ``contexts``, counted page by
    page: the chunks that hold a page the row's one query sees."""
    total = 0
    for ctx in contexts:
        lo = max(int(ctx) - window, 0) if window else 0
        pages = range(lo // block, -(-int(ctx) // block))
        total += len({page // (keys // block) for page in pages})
    return total


@pytest.mark.parametrize('family', ['mistral', 'laguna'])
def test_decode_records_count_the_chunks_the_walk_fetches(
    family, monkeypatch
):
    """``kv_chunks*`` on ``decode`` records is what the rows' contexts
    give, group by group, and ``telemetry['kv_walk_keys']`` names the keys
    a step each pool's walk takes (the rule's, here held to 8 keys, two
    pages, so that rows span several chunks at toy lengths)."""
    from distllm_tpu.ops import paged_attention

    monkeypatch.setattr(paged_attention, 'WALK_MAX_KEYS', 8)
    monkeypatch.setattr(paged_attention, 'WALK_PAGES_A_TURN', 1)
    if family == 'mistral':
        _, _, engine = _tiny_engine(
            attn_backend='interpret', decode_steps=4, max_model_len=96,
        )
        windows, names = {'kv': None}, {'kv': 'kv_chunks'}
        prompts = [list(range(1, 38)), list(range(2, 11)), [5]]
    else:
        from laguna_toy import WINDOW, make_engine, prompt

        _, _, engine = make_engine(attn_backend='interpret')
        windows = {'full': None, 'window': WINDOW}
        names = {'full': 'kv_chunks_full', 'window': 'kv_chunks_window'}
        rng = np.random.default_rng(3)
        prompts = [prompt(rng, 41), prompt(rng, 9)]
    assert engine.telemetry['kv_walk_keys'] == dict.fromkeys(windows, 8)

    seen = []
    reckon = engine._kv_chunks

    def spy(contexts):
        fields = reckon(contexts)
        seen.append((np.array(contexts), fields))
        return fields

    monkeypatch.setattr(engine, '_kv_chunks', spy)
    before = engine.flight.total_recorded
    engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=9)
    )
    assert seen and max(int(c.max()) for c, _ in seen) > 16
    for contexts, fields in seen:
        for group, window in windows.items():
            assert fields[names[group]] == _walked_chunks(
                contexts, 8, window
            ), (group, contexts)
        if family == 'laguna':
            assert fields['kv_chunks'] == fields['kv_chunks_full']
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    decodes = [r for r in records if r['kind'] == 'decode']
    assert [
        {k: v for k, v in r.items() if k.startswith('kv_chunks')}
        for r in decodes
    ] == [fields for _, fields in seen]
    assert not any(k.startswith('kv_turns') for r in decodes for k in r)
    # a chunk holds two pages: the walk fetches fewer chunks than blocks,
    # and no more than one a block
    assert all(0 < r['kv_chunks'] <= r['kv_blocks'] for r in decodes)
    engine.shutdown()


@pytest.mark.parametrize('family', ['mistral', 'laguna'])
def test_chunks_of_known_contexts_reckoned_by_hand(family, monkeypatch):
    """``kv_chunks*`` for a dispatch of known contexts, by hand: chunks of
    16 keys (four pages of 4). Rows at 1, 8, 9, 16, 17 and 40 tokens walk
    1, 1, 1, 1, 2 and 3 chunks; under a window of 12 the last row's floor,
    28, is in its chunk 1: 2 chunks. Nothing else rides the records beside
    them (``kv_turns*`` went with PR 50: no sound metric could be made of
    it)."""
    from distllm_tpu.ops import paged_attention

    monkeypatch.setattr(paged_attention, 'WALK_MAX_KEYS', 16)
    if family == 'mistral':
        _, _, engine = _tiny_engine(attn_backend='interpret', max_model_len=96)
    else:
        from laguna_toy import WINDOW, make_engine

        assert WINDOW == 12
        _, _, engine = make_engine(attn_backend='interpret')
    fields = engine._kv_chunks(np.array([1, 8, 9, 16, 17, 40]))
    if family == 'mistral':
        assert fields == {'kv_chunks': 9}
    else:
        assert fields == {
            'kv_chunks_full': 9, 'kv_chunks': 9, 'kv_chunks_window': 8,
        }
    engine.shutdown()


def test_no_walk_no_chunk_count():
    """Under the XLA backend nothing walks: no telemetry entry and no
    ``kv_chunks`` on the records."""
    _, _, engine = _tiny_engine(attn_backend='xla')
    assert 'kv_walk_keys' not in engine.telemetry
    before = engine.flight.total_recorded
    engine.generate_ids(
        [[1, 2, 3]], SamplingParams(temperature=0.0, max_tokens=6)
    )
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    decodes = [r for r in records if r['kind'] == 'decode']
    assert decodes and not any(
        'kv_chunks' in r or 'kv_turns' in r for r in decodes
    )
    engine.shutdown()
