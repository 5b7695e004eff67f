"""Sampled speculative verification (docs/speculative.md "Sampled
verification"): distribution preservation of the device-side rejection
sampler (chi-square on a tiny vocab), the analytic point-mass q edge
cases, cross-kernel sampled parity (decode scan vs. spec 'none' verify
window in fp32), filter parity, and engine-level determinism with
accepted drafts at temperature > 0."""

import numpy as np

import jax
import jax.numpy as jnp

from distllm_tpu.generate.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.models import mistral
from distllm_tpu.ops.sampling import filter_logits, verify_spans


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


class _StubDrafter:
    def __init__(self, proposals):
        self.proposals = list(proposals)

    def draft(self, history, k):
        start = len(history)
        return self.proposals[start:start + k]


def _force_drafts(engine, rid, proposals, prompt_len):
    pad = [0] * prompt_len
    engine._requests[rid].drafter = _StubDrafter(pad + list(proposals))


# ------------------------------------------------- verify_spans op level
def _verify_batch(logits_row, draft, n, temperature=1.0, top_p=1.0,
                  min_p=0.0, top_k=0, top_window=0):
    """Run ``n`` independent single-draft spans (distinct seeds) of the
    same logits row through verify_spans; returns the packed [n, 3]."""
    vocab = len(logits_row)
    span_logits = jnp.broadcast_to(
        jnp.asarray(logits_row, jnp.float32)[None, None, :], (n, 2, vocab)
    )
    span_ids = jnp.broadcast_to(
        jnp.asarray([0, draft], jnp.int32)[None, :], (n, 2)
    )
    span_lens = jnp.full((n,), 2, jnp.int32)
    span_positions = jnp.broadcast_to(
        jnp.asarray([3, 4], jnp.int32)[None, :], (n, 2)
    )
    ones = jnp.ones((n,), jnp.float32)
    packed = verify_spans(
        span_logits, span_ids, span_lens, span_positions,
        ones * temperature, ones * top_p, ones * min_p,
        jnp.full((n,), top_k, jnp.int32),
        jnp.arange(n, dtype=jnp.uint32),
        top_window=top_window,
    )
    return np.asarray(packed)


def _expected_probs(logits_row, temperature=1.0, top_p=1.0, min_p=0.0,
                    top_k=0):
    """The served distribution p̃ as a dense [V] numpy vector, via the
    same filter_logits the kernels use."""
    filtered = filter_logits(
        jnp.asarray(logits_row, jnp.float32)[None, :],
        jnp.asarray([temperature], jnp.float32),
        jnp.asarray([top_p], jnp.float32),
        jnp.asarray([min_p], jnp.float32),
        top_k=jnp.asarray([top_k], jnp.int32),
    )
    filtered = np.asarray(filtered)[0]  # vocabulary order
    finite = np.isfinite(filtered)
    dense = np.zeros_like(filtered, dtype=np.float64)
    dense[finite] = np.exp(filtered[finite] - filtered[finite].max())
    return dense / dense.sum()


def _chi_square(counts, probs, n):
    expected = probs * n
    keep = expected > 0
    return float(((counts[keep] - expected[keep]) ** 2
                  / expected[keep]).sum())


def test_rejection_sampling_preserves_target_distribution():
    """The marginal of the FIRST emitted token (draft if accepted, else
    residual resample) must equal the served distribution p̃ exactly —
    the defining property of speculative sampling. Chi-square over 4096
    deterministic seeded trials on an 8-token vocab; df = 7, threshold
    35 sits past the 1e-4 tail, and a wrong distribution scales the
    statistic with N (thousands, not tens)."""
    rng = np.random.default_rng(42)
    logits_row = rng.normal(0.0, 1.5, size=8)
    n = 4096
    draft = 3
    packed = _verify_batch(logits_row, draft, n)
    emitted = packed[:, 0]
    counts = np.bincount(emitted, minlength=8).astype(float)
    probs = _expected_probs(logits_row)
    assert _chi_square(counts, probs, n) < 35.0
    # The acceptance rate itself is p̃(draft) for a point-mass q.
    accept_rate = packed[:, -1].mean()
    assert abs(accept_rate - probs[draft]) < 0.05


def test_rejection_sampling_preserves_filtered_distribution():
    """Same chi-square contract with top-p + top-k active: emitted
    tokens stay inside the kept set and follow the renormalized
    filtered target."""
    rng = np.random.default_rng(7)
    logits_row = rng.normal(0.0, 1.5, size=8)
    n = 4096
    draft = int(np.argsort(logits_row)[-2])  # second-likeliest: in-set
    packed = _verify_batch(
        logits_row, draft, n, top_p=0.8, top_k=5,
    )
    emitted = packed[:, 0]
    probs = _expected_probs(logits_row, top_p=0.8, top_k=5)
    kept = set(np.flatnonzero(probs > 0).tolist())
    assert set(emitted.tolist()) <= kept
    counts = np.bincount(emitted, minlength=8).astype(float)
    assert _chi_square(counts, probs, n) < 35.0


def test_point_mass_draft_on_sole_support_always_accepts():
    """top_k=1 with the draft equal to the argmax: the kept set is
    exactly {draft}, so p̃(draft) = 1 and every trial accepts (the
    residual is empty; the bonus slot falls back to the full filtered
    target, which is again the argmax)."""
    rng = np.random.default_rng(3)
    logits_row = rng.normal(0.0, 1.5, size=8)
    argmax = int(np.argmax(logits_row))
    packed = _verify_batch(logits_row, argmax, 256, top_k=1)
    assert (packed[:, -1] == 1).all()
    assert (packed[:, 0] == argmax).all()
    assert (packed[:, 1] == argmax).all()  # bonus = sole survivor


def test_point_mass_draft_outside_kept_set_never_accepts():
    """top_k=1 with a non-argmax draft: p̃(draft) = 0, so acceptance
    probability is exactly zero and the correction resamples the kept
    set (the argmax, its only member)."""
    rng = np.random.default_rng(3)
    logits_row = rng.normal(0.0, 1.5, size=8)
    argmax = int(np.argmax(logits_row))
    draft = (argmax + 1) % 8
    packed = _verify_batch(logits_row, draft, 256, top_k=1)
    assert (packed[:, -1] == 0).all()
    assert (packed[:, 0] == argmax).all()


def test_greedy_rows_keep_argmax_semantics():
    """temperature == 0 rows are untouched by the sampler: out is the
    argmax everywhere and a draft is accepted iff it equals it."""
    rng = np.random.default_rng(11)
    logits_row = rng.normal(0.0, 1.5, size=8)
    argmax = int(np.argmax(logits_row))
    hit = _verify_batch(logits_row, argmax, 4, temperature=0.0)
    miss = _verify_batch(
        logits_row, (argmax + 1) % 8, 4, temperature=0.0
    )
    assert (hit[:, 0] == argmax).all() and (hit[:, -1] == 1).all()
    assert (miss[:, 0] == argmax).all() and (miss[:, -1] == 0).all()


def test_verify_spans_deterministic_per_seed():
    rng = np.random.default_rng(5)
    logits_row = rng.normal(0.0, 1.5, size=8)
    a = _verify_batch(logits_row, 2, 64)
    b = _verify_batch(logits_row, 2, 64)
    assert (a == b).all()
    # Distinct seeds (rows here) actually decorrelate the draws.
    assert len(set(a[:, 0].tolist())) > 1


# ---------------------------------------------- cross-kernel parity (fp32)
def _sampled_outputs(engine, prompts, budgets, **sp_kw):
    rids = [
        engine.add_request(
            p, SamplingParams(max_tokens=n, seed=100 + i, **sp_kw)
        )
        for i, (p, n) in enumerate(zip(prompts, budgets))
    ]
    engine._run_to_completion()
    return [engine._finished.pop(r).output_ids for r in rids]


def _parity_workload(vocab):
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(1, vocab, size=n)) for n in (5, 11, 3)]
    budgets = [6, 4, 7]
    return prompts, budgets


def test_spec_none_matches_decode_scan_when_sampled():
    """'none' structural baseline at temperature > 0: draft_k > 0 with
    drafting disabled rides the verify kernel with span length 1, and the
    counter-based PRNG makes its sampled stream BIT-IDENTICAL (fp32) to
    the classic decode scan at draft_k = 0."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompts, budgets = _parity_workload(cfg.vocab_size)
    sp = dict(temperature=0.8)
    classic = _sampled_outputs(
        _engine(cfg, params), prompts, budgets, **sp
    )
    spec_none = _engine(cfg, params, draft_k=4, spec_draft_source='none')
    none_out = _sampled_outputs(spec_none, prompts, budgets, **sp)
    assert spec_none._stats['spec_windows'] > 0
    assert classic == none_out


def test_spec_filter_parity_with_decode_scan_when_sampled():
    """top-p/top-k parity: the verify kernel applies the same
    filter_logits as plain decode, so filtered sampled streams agree
    across kernels too (fp32)."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompts, budgets = _parity_workload(cfg.vocab_size)
    sp = dict(temperature=0.9, top_p=0.9, top_k=8)
    classic = _sampled_outputs(
        _engine(cfg, params), prompts, budgets, **sp
    )
    spec_none = _engine(cfg, params, draft_k=4, spec_draft_source='none')
    none_out = _sampled_outputs(spec_none, prompts, budgets, **sp)
    assert spec_none._stats['spec_windows'] > 0
    assert classic == none_out


# ----------------------------------------------------- engine determinism
def test_engine_sampled_spec_deterministic_with_accepts():
    """Two fresh engines, the same (seed, schedule), temperature > 0
    with top_k=1, drafts forced to the greedy reference: the filtered
    target is a point mass on the argmax, so p̃(draft) = 1 and every
    reference draft is accepted by the rejection sampler — a nonzero
    accepted count that does not hinge on the tiny random model's
    near-flat logits. Outputs are identical across runs AND equal to
    the greedy reference."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 12]
    n = 9
    ref = _dense_greedy_reference(cfg, params, prompt, n)

    def run():
        eng = _engine(cfg, params, draft_k=4)
        rid = eng.add_request(
            prompt,
            SamplingParams(
                temperature=0.9, top_k=1, max_tokens=n, seed=7
            ),
        )
        _force_drafts(eng, rid, ref + [0] * 8, len(prompt))
        eng._run_to_completion()
        out = eng._finished.pop(rid).output_ids
        return out, dict(eng._stats)

    out1, st1 = run()
    out2, st2 = run()
    assert out1 == out2 == ref
    assert st1['spec_accepted_tokens'] > 0
    assert st1['spec_sampled_rows'] > 0
    assert st1['spec_accepted_tokens'] == st2['spec_accepted_tokens']


def test_engine_sampled_spec_deterministic_unfiltered():
    """Determinism without filters: a genuinely stochastic request
    (near-flat tiny-model logits at temperature 0.8) under speculation
    reproduces bit-for-bit across fresh engines."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 12]
    n = 9

    def run():
        eng = _engine(cfg, params, draft_k=4)
        rid = eng.add_request(
            prompt,
            SamplingParams(temperature=0.8, max_tokens=n, seed=7),
        )
        eng._run_to_completion()
        return eng._finished.pop(rid).output_ids

    out1, out2 = run(), run()
    assert out1 == out2
    assert len(out1) == n


def test_engine_sampled_spec_seed_changes_stream():
    """The explicit per-request seed is load-bearing: a different seed
    yields a different sampled stream under speculation."""
    cfg = _tiny_cfg()
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 12]
    n = 12

    def run(seed):
        eng = _engine(cfg, params, draft_k=4)
        rid = eng.add_request(
            prompt,
            SamplingParams(temperature=1.2, max_tokens=n, seed=seed),
        )
        eng._run_to_completion()
        return eng._finished.pop(rid).output_ids

    assert run(7) != run(8)
