"""Generation engine tests: the engine against the dense forward's golden
decoding (prefill, continuous batching, preemption, stop tokens, quantized
weights). Its units: ``test_engine_units.py``; the window's forms:
``test_engine_windows.py``; mixed windows: ``test_engine_mixed.py``."""

import numpy as np
import pytest

import jax

from distllm_tpu.generate.engine import EngineConfig, LLMEngine, SamplingParams
from distllm_tpu.models import mistral


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
