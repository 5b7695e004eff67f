"""The engine against the dense forward over the decode window's forms:
steps a window, pipelined preemption, a budget below the window, gemma2's layers, deferred prefill."""

import numpy as np

import jax

from distllm_tpu.generate.engine import EngineConfig, LLMEngine, SamplingParams
from distllm_tpu.models import mistral
from test_engine import _dense_greedy_reference, _expect_short_answers, _tiny_engine


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
