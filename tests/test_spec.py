"""Prompt-lookup speculative decoding (docs/speculative.md): drafter
units, the greedy on/off identity matrix, the acceptance-rule edge
matrix, rejected-suffix rollback state equality, and the per-accepted-
token TPOT/goodput accounting."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.generate.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.generate.engine.spec import PromptLookupDrafter
from distllm_tpu.models import mistral


class IdTokenizer:
    eos_id = None

    def decode(self, ids):
        return ' '.join(str(i) for i in ids)


def _tiny_cfg(**kw):
    base = dict(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    base.update(kw)
    return mistral.MistralConfig(**base)


def _engine(model_cfg, params, **cfg_kw):
    base = dict(
        block_size=4, num_blocks=96, max_num_seqs=2, max_model_len=96,
        prefer_native_allocator=False,
    )
    base.update(cfg_kw)
    return LLMEngine(model_cfg, params, IdTokenizer(), EngineConfig(**base))


def _dense_greedy_reference(cfg, params, prompt, n_tokens):
    ids = list(prompt)
    for _ in range(n_tokens):
        arr = np.asarray([ids], np.int32)
        hidden = mistral.apply(params, cfg, arr, np.ones_like(arr))
        lg = mistral.logits(params, cfg, hidden[:, -1])
        ids.append(int(np.argmax(np.asarray(lg)[0])))
    return ids[len(prompt):]


_STAGGER_PROMPT_LENS = (5, 21, 3, 33, 7, 13)
_STAGGER_OUT_LENS = (3, 17, 9, 5, 12, 8)


def _stagger_prompts(vocab, seed=1):
    """The mixed-window staggered serving workload, plus repetition: two
    prompts share a 2-block prefix (cache-hit tails), long prompts chunk,
    and half the prompts tile an n-gram motif so the prompt-lookup
    drafter has material."""
    rng = np.random.default_rng(seed)
    prompts = [
        list(rng.integers(1, vocab, size=n)) for n in _STAGGER_PROMPT_LENS
    ]
    shared = list(rng.integers(1, vocab, size=8))
    motif = list(rng.integers(1, vocab, size=4))
    for i in (1, 3):
        prompts[i] = (motif * (1 + len(prompts[i]) // 4))[: len(prompts[i])]
    prompts[0] = shared + prompts[0]
    prompts[4] = shared + prompts[4]
    return prompts


def _run_stagger(engine, vocab, seed=1):
    prompts = _stagger_prompts(vocab, seed)
    rids = [
        engine.add_request(p, SamplingParams(temperature=0.0, max_tokens=n))
        for p, n in zip(prompts, _STAGGER_OUT_LENS)
    ]
    engine._run_to_completion()
    return [engine._finished.pop(r).output_ids for r in rids]


# --------------------------------------------------------------- drafter
def test_drafter_proposes_latest_continuation():
    d = PromptLookupDrafter(ngram=2)
    history = [1, 2, 3, 9, 1, 2, 4, 7, 1, 2]
    # Final 2-gram (1, 2) last occurred at positions 4-5 -> continuation
    # [4, 7, 1, 2] (most recent match wins over the 0-1 occurrence).
    assert d.draft(history, 4) == [4, 7, 1, 2]
    assert d.draft(history, 2) == [4, 7]


def test_drafter_no_match_and_short_history():
    d = PromptLookupDrafter(ngram=3)
    assert d.draft([1, 2], 4) == []  # shorter than the n-gram
    assert d.draft([1, 2, 3, 4, 5], 4) == []  # (3,4,5) never seen before
    assert d.draft([1, 2, 3], 0) == []  # k == 0


def test_drafter_incremental_observation():
    d = PromptLookupDrafter(ngram=2)
    assert d.draft([5, 6, 7], 3) == []
    # Growing the history indexes only the new positions; the (5, 6)
    # occurrence is found once the suffix repeats it.
    assert d.draft([5, 6, 7, 5, 6], 3) == [7, 5, 6]
    # Terminal n-gram is never indexed against itself: a history ending
    # in its only occurrence proposes nothing rather than [].
    d2 = PromptLookupDrafter(ngram=2)
    assert d2.draft([1, 2, 3, 4], 3) == []


def test_drafter_rejects_bad_ngram():
    with pytest.raises(ValueError):
        PromptLookupDrafter(ngram=0)


# ------------------------------------------------- ragged rollback (op)
def test_ragged_decode_row_ignores_stale_suffix_kv(rng):
    """Rejected-draft K/V sits at positions >= the row's context; the
    ragged kernel must mask it out of every later query, which is the
    whole device-side rollback story (docs/speculative.md)."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention_xla,
        write_chunk_kv,
    )

    block_size = 4
    k_cache = jnp.asarray(
        rng.normal(size=(8, block_size, 2 * 8)).astype(np.float32)
    )
    v_cache = jnp.asarray(
        rng.normal(size=(8, block_size, 2 * 8)).astype(np.float32)
    )
    block_tables = jnp.asarray([[2, 5]], dtype=jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, 1, 4, 8)).astype(np.float32))
    q_positions = jnp.asarray([[5]], dtype=jnp.int32)
    context_lens = jnp.asarray([6], dtype=jnp.int32)
    clean = np.asarray(
        ragged_paged_attention_xla(
            q, k_cache, v_cache, block_tables, context_lens, q_positions,
            q_lens=jnp.asarray([1], jnp.int32),
        )
    )
    # Trash the suffix positions 6..7 (a rejected draft's writes).
    junk_k = jnp.full((1, 2, 2, 8), 1e9, jnp.float32)
    junk_v = jnp.full((1, 2, 2, 8), -1e9, jnp.float32)
    k_dirty, v_dirty = write_chunk_kv(
        k_cache, v_cache, junk_k, junk_v, block_tables,
        jnp.asarray([[6, 7]], jnp.int32), jnp.ones((1, 2), bool),
    )
    dirty = np.asarray(
        ragged_paged_attention_xla(
            q, k_dirty, v_dirty, block_tables, context_lens, q_positions,
            q_lens=jnp.asarray([1], jnp.int32),
        )
    )
    np.testing.assert_array_equal(clean, dirty)


# ------------------------------------------------------ identity matrix
def test_spec_token_identity_fast_canary():
    """Fast-tier spec on/off identity canary (fp32): prefix cache +
    chunked config on the staggered workload, and drafting must actually
    fire. The full matrix (sliding window, gemma2, mixed) runs in the
    slow tier."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    kw = dict(enable_prefix_cache=True, prefill_chunk_tokens=4)
    off = _run_stagger(
        _engine(cfg, params, draft_k=0, **kw), cfg.vocab_size
    )
    eng = _engine(cfg, params, draft_k=4, **kw)
    on = _run_stagger(eng, cfg.vocab_size)
    assert on == off
    assert eng._stats['spec_windows'] > 0
    assert eng._stats['spec_draft_tokens'] > 0
    assert eng._stats['spec_accepted_tokens'] > 0


@pytest.mark.slow
@pytest.mark.parametrize(
    'cfg_kw, engine_kw',
    [
        ({}, {}),
        ({}, {'enable_prefix_cache': True}),
        ({}, {'enable_prefix_cache': True, 'prefill_chunk_tokens': 4}),
        ({'sliding_window': 4}, {'prefill_chunk_tokens': 4}),
        (
            {},
            {
                'enable_mixed_batching': True,
                'enable_prefix_cache': True,
                'prefill_chunk_tokens': 4,
                'max_window_prefill_tokens': 8,
                'max_window_prefill_seqs': 2,
            },
        ),
    ],
    ids=[
        'plain', 'prefix_cache', 'prefix_cache_chunked', 'sliding_window',
        'mixed_batching',
    ],
)
def test_spec_token_identity_matrix(cfg_kw, engine_kw):
    """Greedy speculation on/off is token-identical across the engine
    identity matrix (fp32 — the regime where the decode-scan and ragged
    kernels agree bitwise; docs/speculative.md covers the bf16 kernel-
    universe caveat and its structural test below)."""
    cfg = _tiny_cfg(**cfg_kw)
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    off = _run_stagger(
        _engine(cfg, params, draft_k=0, **engine_kw), cfg.vocab_size
    )
    eng = _engine(cfg, params, draft_k=4, **engine_kw)
    on = _run_stagger(eng, cfg.vocab_size)
    assert on == off
    assert eng._stats['spec_windows'] > 0
    if engine_kw.get('enable_mixed_batching'):
        # Chunk spans actually rode verify windows (mixed composition).
        assert eng._stats.get('spec_chunk_windows', 0) > 0
        assert eng._stats.get('mixed_prefill_tokens', 0) > 0


@pytest.mark.slow
def test_spec_token_identity_gemma2():
    """gemma2 serving (alternating windows, softcaps, sandwich norms,
    query_scale) through speculative windows stays token-exact."""
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
    params = gemma.init(jax.random.PRNGKey(1), cfg)
    off = _run_stagger(
        _engine(cfg, params, draft_k=0, prefill_chunk_tokens=4),
        cfg.vocab_size,
    )
    eng = _engine(cfg, params, draft_k=4, prefill_chunk_tokens=4)
    on = _run_stagger(eng, cfg.vocab_size)
    assert on == off
    assert eng._stats['spec_windows'] > 0


def test_spec_structural_identity_bf16():
    """Drafting on vs off INSIDE the verify kernel is bit-identical even
    in bf16 (same fixed-shape executable; valid columns are independent
    of draft-column content) — the structural half of the bit-identity
    story that the gen_spec bench stage asserts on chip. Cross-KERNEL
    identity (vs the decode scan) is fp32-only: two compiled programs
    may round a near-tied bf16 logit differently."""
    cfg = _tiny_cfg(vocab_size=256, hidden_size=64, intermediate_size=128,
                    dtype='bfloat16')
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    null = _run_stagger(
        _engine(cfg, params, draft_k=4, spec_draft_source='none',
                enable_prefix_cache=True),
        cfg.vocab_size,
    )
    eng = _engine(cfg, params, draft_k=4, enable_prefix_cache=True)
    on = _run_stagger(eng, cfg.vocab_size)
    assert on == null
    assert eng._stats['spec_accepted_tokens'] > 0


# -------------------------------------------------- acceptance edge matrix
class _StubDrafter:
    """Deterministic proposals for the acceptance-rule edge matrix."""

    def __init__(self, proposals):
        self.proposals = list(proposals)

    def draft(self, history, k):
        start = len(history)
        return self.proposals[start:start + k]


def _force_drafts(engine, rid, proposals, prompt_len):
    """Install a stub drafter proposing ``proposals`` (indexed by
    absolute history position past the prompt)."""
    pad = [0] * prompt_len
    engine._requests[rid].drafter = _StubDrafter(pad + list(proposals))


def test_acceptance_all_accepted_matches_reference():
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 12]
    n = 9
    ref = _dense_greedy_reference(cfg, params, prompt, n)
    eng = _engine(cfg, params, draft_k=4)
    rid = eng.add_request(
        prompt, SamplingParams(temperature=0.0, max_tokens=n)
    )
    # Propose the exact greedy continuation: every draft must be accepted
    # (ref[i] is the token at history position len(prompt)+i; drafts for
    # a history ending at position p propose ref[p-len(prompt):]).
    _force_drafts(eng, rid, ref + [0] * 8, len(prompt))
    eng._run_to_completion()
    assert eng._finished.pop(rid).output_ids == ref
    # 9 tokens in 1 prefill emission + ceil(8 / (1+4)) spec windows:
    # full drafts accepted -> far fewer windows than tokens.
    assert eng._stats['spec_accepted_tokens'] > 0
    assert (
        eng._stats['spec_accepted_tokens']
        == eng._stats['spec_draft_tokens']
    )
    assert eng._stats['spec_windows'] < n


def test_acceptance_zero_accepted_matches_reference():
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompt = [7, 3, 22]
    n = 6
    ref = _dense_greedy_reference(cfg, params, prompt, n)
    eng = _engine(cfg, params, draft_k=3)
    rid = eng.add_request(
        prompt, SamplingParams(temperature=0.0, max_tokens=n)
    )
    # Propose deliberately wrong tokens: nothing accepted, output exact.
    wrong = [(t + 1) % cfg.vocab_size for t in ref] + [1] * 8
    _force_drafts(eng, rid, wrong, len(prompt))
    eng._run_to_completion()
    assert eng._finished.pop(rid).output_ids == ref
    assert eng._stats['spec_accepted_tokens'] == 0
    assert eng._stats['spec_draft_tokens'] > 0


def test_acceptance_eos_inside_accepted_prefix():
    """EOS (a stop token) accepted mid-span finishes the request there;
    the already-verified suffix is discarded, not emitted."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 12]
    ref = _dense_greedy_reference(cfg, params, prompt, 8)
    stop = ref[3]
    eng = _engine(cfg, params, draft_k=4)
    rid = eng.add_request(
        prompt,
        SamplingParams(
            temperature=0.0, max_tokens=20, stop_token_ids=(stop,)
        ),
    )
    _force_drafts(eng, rid, ref + [0] * 16, len(prompt))
    eng._run_to_completion()
    # Raw output_ids keep the stop token (generate_ids strips it): the
    # stream must end EXACTLY at the stop, the verified suffix discarded.
    out = eng._finished.pop(rid).output_ids
    assert out == ref[: ref.index(stop) + 1]


def test_acceptance_preemption_mid_draft():
    """A pool too small for every row forces recompute preemption between
    verify windows; outputs stay exact and no blocks leak."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    from test_engine import _expect_short_answers

    eng = _engine(
        cfg, params, draft_k=4, num_blocks=10, max_num_seqs=3,
        max_model_len=64,
    )
    victims = _expect_short_answers(eng)
    prompts = [[5, 9, 12], [7, 3, 22, 31], [1, 2, 3, 4, 5]]
    n = 12
    rids = [
        eng.add_request(p, SamplingParams(temperature=0.0, max_tokens=n))
        for p in prompts
    ]
    eng._run_to_completion()
    for prompt, rid in zip(prompts, rids):
        ref = _dense_greedy_reference(cfg, params, prompt, n)
        assert eng._finished.pop(rid).output_ids == ref
    assert victims() > 0
    assert eng.sched.num_free_blocks == 9  # no leaks


def test_temperature_rows_draft_with_sampled_verification():
    """Stochastic rows draft too: device-side rejection sampling verifies
    their spans (docs/speculative.md "Sampled verification"). The stub
    drafter guarantees proposals regardless of what the sampled history
    looks like (prompt-lookup matches would be luck on a random model)."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    eng = _engine(cfg, params, draft_k=4)
    prompt = [5, 9, 12, 5, 9, 12]
    rid = eng.add_request(
        prompt, SamplingParams(temperature=0.9, max_tokens=7)
    )
    # Sampled rows get the real prompt-lookup drafter attached now (the
    # old greedy-only gate is gone) ...
    assert eng._requests[rid].drafter is not None
    # ... which the stub then replaces so drafting is deterministic here.
    _force_drafts(eng, rid, [7] * 16, len(prompt))
    eng._run_to_completion()
    assert len(eng._finished.pop(rid).output_ids) == 7
    assert eng._stats.get('spec_draft_tokens', 0) > 0
    assert eng._stats['spec_windows'] > 0


# ------------------------------------------- rejected-suffix rollback state
def test_rejected_suffix_rolls_back_to_never_drafted_state():
    """After a window whose drafts are ALL rejected, KV block rows, the
    scheduler free list (content AND order), and PrefixCache refcounts
    must equal a never-drafted run at the same point — the rollback
    contract (per-row reservation + sched.trim)."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 12, 4, 7, 3, 22, 31]  # 2 full blocks for the cache

    def run_one_window(draft_k, wrong_drafts):
        eng = _engine(
            cfg, params, draft_k=draft_k, enable_prefix_cache=True,
            decode_steps=1, pipeline_depth=1,
        )
        rid = eng.add_request(
            prompt, SamplingParams(temperature=0.0, max_tokens=8)
        )
        if wrong_drafts:
            ref = _dense_greedy_reference(cfg, params, prompt, 8)
            _force_drafts(
                eng, rid, [(t + 1) % cfg.vocab_size for t in ref] + [1] * 8,
                len(prompt),
            )
        # Admit + prefill, then exactly two decode/verify windows.
        for _ in range(2):
            eng.step()
        return eng, rid

    spec, rid_a = run_one_window(4, wrong_drafts=True)
    base, rid_b = run_one_window(0, wrong_drafts=False)
    assert spec._stats['spec_draft_tokens'] > 0
    assert spec._stats['spec_accepted_tokens'] == 0
    a, b = spec._requests[rid_a], base._requests[rid_b]
    assert a.output_ids == b.output_ids
    assert spec.sched.block_row(rid_a) == base.sched.block_row(rid_b)
    assert spec.sched.num_free_blocks == base.sched.num_free_blocks
    # Free-list CONTENT equality, not just count (PyScheduler backend).
    assert spec.sched._inner._free == base.sched._inner._free
    # PrefixCache state: same inserted digests, same refcounts.
    pc_a, pc_b = spec.prefix_cache, base.prefix_cache
    assert set(pc_a._entries) == set(pc_b._entries)
    for digest, entry in pc_a._entries.items():
        assert entry.refcount == pc_b._entries[digest].refcount


# ---------------------------------------- accounting, metrics, and flight
def test_tpot_and_goodput_count_accepted_tokens():
    """distllm_request_tpot_seconds divides by ACCEPTED TOKENS (n_out-1)
    and distllm_engine_goodput_tokens_total advances by accepted tokens,
    not windows — multi-token speculative windows must not deflate
    either series."""
    from distllm_tpu.observability import instruments as metrics

    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    n = 9
    eng = _engine(cfg, params, draft_k=4, ttft_slo_s=60.0)
    ref = _dense_greedy_reference(cfg, params, [5, 9, 12], n)
    goodput_before = metrics.GOODPUT_TOKENS.value
    tpot_count_before = metrics.REQUEST_TPOT.count
    tpot_sum_before = metrics.REQUEST_TPOT.sum
    rid = eng.add_request(
        [5, 9, 12], SamplingParams(temperature=0.0, max_tokens=n)
    )
    _force_drafts(eng, rid, ref + [0] * 8, len([5, 9, 12]))
    eng._run_to_completion()
    request = eng._finished[rid]
    n_out = len(request.output_ids)
    assert n_out == n
    # Goodput counts every accepted token of the SLO-met request.
    assert metrics.GOODPUT_TOKENS.value - goodput_before == n_out
    assert eng._stats['goodput_tokens'] == n_out
    # TPOT: one observation per finished request, normalized per token —
    # (finish - first) / (n_out - 1), so several tokens landing in one
    # verify window measure as genuinely fast tokens, not one window.
    assert metrics.REQUEST_TPOT.count - tpot_count_before == 1
    observed = metrics.REQUEST_TPOT.sum - tpot_sum_before
    expected = (request.t_finish - request.t_first_token) / (n_out - 1)
    assert observed == pytest.approx(expected)
    # Fewer windows than tokens (speculation!) yet full token accounting.
    assert eng._stats['spec_windows'] < n_out


def test_spec_flight_records_and_metrics():
    """Verify windows record kind='spec' with draft/accepted payloads and
    the distllm_engine_spec_* series advance."""
    from distllm_tpu.observability import instruments as metrics
    from distllm_tpu.observability.flight import get_flight_recorder

    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    before = len(
        [r for r in get_flight_recorder().snapshot() if r['kind'] == 'spec']
    )
    windows_before = metrics.SPEC_WINDOWS.value
    drafts_before = metrics.SPEC_DRAFT_TOKENS.value
    accepted_before = metrics.SPEC_ACCEPTED_TOKENS.value
    eng = _engine(cfg, params, draft_k=4)
    _run_stagger(eng, cfg.vocab_size)
    records = [
        r for r in get_flight_recorder().snapshot() if r['kind'] == 'spec'
    ]
    assert len(records) > before
    rec = records[-1]
    assert 'draft_tokens' in rec and 'accepted_tokens' in rec
    assert metrics.SPEC_WINDOWS.value > windows_before
    assert metrics.SPEC_DRAFT_TOKENS.value > drafts_before
    assert metrics.SPEC_ACCEPTED_TOKENS.value >= accepted_before


# ----------------------------------------------------------- validation
def test_spec_config_validation():
    with pytest.raises(ValueError, match='draft_k'):
        EngineConfig(draft_k=-1)
    with pytest.raises(ValueError, match='spec_ngram'):
        EngineConfig(spec_ngram=0)
    with pytest.raises(ValueError, match='mutually exclusive'):
        EngineConfig(draft_k=4, defer_prefill=True)
    with pytest.raises(ValueError, match='spec_draft_source'):
        EngineConfig(spec_draft_source='oracle')
    # Normal composition stays legal.
    assert EngineConfig(
        draft_k=4, enable_mixed_batching=True, prefill_chunk_tokens=16
    ).draft_k == 4


def test_tpu_generator_config_allows_spec_with_temperature():
    # Sampled verification lifted the old greedy-only rejection: draft_k
    # composes with temperature > 0 (docs/speculative.md "Sampled
    # verification").
    from distllm_tpu.generate.generators.tpu_backend import (
        TpuGeneratorConfig,
    )

    cfg = TpuGeneratorConfig(
        pretrained_model_name_or_path='/tmp/x', temperature=0.5,
        draft_k=4,
    )
    assert cfg.draft_k == 4
    cfg = TpuGeneratorConfig(
        pretrained_model_name_or_path='/tmp/x', temperature=0.0, draft_k=4,
    )
    assert cfg.draft_k == 4
