"""``models/sdar.py`` (``sdar_moe``: generation by diffusion over blocks)
against ``benchmarks/reference_sdar.py`` on seeded weights at toy widths:
the dense forward under the block-causal mask; the paged prefill and the
block window against the reference's full forwards at EVERY denoise step;
what the pages hold; the unmasking rule and the confidence against their
NumPy twins; the experts' shares; and the wrong programs, each of which has
to break a tolerance. The engine over it: ``tests/test_engine_families*.py``
(its row: ``tests/sdar_toy.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import sdar_toy as toy

from benchmarks import reference_sdar as ref
from distllm_tpu.models import moe, sdar
from distllm_tpu.ops import sampling

B = toy.BLOCK
WIDTH = 32  # every reference forward is padded to one compiled shape
LIMIT = 2e-4  # of the reference's spread: float32 on both sides


@pytest.fixture(scope='module')
def model():
    return toy.tiny(0)


def spread(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / b.std())


def replay_error(params, hf, prompt_ids, tokens, decided_at, logits, steps):
    """The largest distance of a denoise forward's logits from the
    reference's, over every step of every block of a window."""
    whole = len(prompt_ids) // B * B
    context, worst = list(prompt_ids[:whole]), 0.0
    for j in range(len(tokens) // B):
        given = prompt_ids[whole:] if j == 0 else []
        block = slice(j * B, (j + 1) * B)
        for s, (want, _) in enumerate(ref.replay_block(
            params, hf, context, given, tokens[block], decided_at[block],
            steps, width=WIDTH,
        )):
            worst = max(worst, spread(logits[j, s], want))
        context += [int(t) for t in tokens[block]]
    return worst


def test_dense_forward_is_the_references_under_the_block_causal_mask(model):
    hf, cfg, params = model
    ids = np.asarray(toy.prompt(np.random.default_rng(0), 14))[None]
    hidden = sdar.apply(params, cfg, jnp.asarray(ids), jnp.ones_like(ids))
    want, _ = ref.forward(params, hf, ids[0])
    assert spread(sdar.logits(params, cfg, hidden)[0], want) < LIMIT
    # and the mask is the block's: position 12 sees 13 (its block), not 8's
    # block's successor alone; a causal reference differs
    causal, _ = ref.forward(params, {**hf, 'block_length': 1}, ids[0])
    assert spread(causal, want) > 100 * LIMIT
    mask = np.asarray(sdar.block_mask(jnp.arange(8), 4))
    assert mask[0, 3] and not mask[3, 4] and mask[4, 7] and mask[7, 0]


@pytest.mark.parametrize('backend, remainder, steps, threshold', [
    *[('xla', r, s, 0.3 if (r + s) % 2 else None)
      for r in range(B) for s in (1, 2, 4)],
    ('interpret', 0, 4, None), ('interpret', 1, 2, 0.3),
    ('interpret', 2, 1, None), ('interpret', 3, 4, 0.3),
])
def test_paged_prefill_then_block_windows_are_the_references(
    model, backend, remainder, steps, threshold
):
    """Every denoise forward's logits, the prefill's, the counters, and the
    pages: they hold the DECIDED blocks' K/V (what the commit wrote)."""
    hf, cfg, params = model
    rng = np.random.default_rng(10 * remainder + steps)
    prompt_ids = toy.prompt(rng, 16 + remainder)
    tokens, at, logits, prefill, (k, v), row, counters = toy.paged_run(
        cfg, params, prompt_ids, 2, steps=steps, threshold=threshold,
        backend=backend, sampling=(0.7, 0.9, 0), span=8,
    )
    want, _ = ref.forward(params, hf, prompt_ids[:16], width=WIDTH)
    assert spread(prefill, want) < LIMIT
    assert list(tokens[:remainder]) == prompt_ids[16:]
    assert list(at[:remainder]) == [-1] * remainder
    assert set(at[remainder:]) <= set(range(steps))
    assert replay_error(params, hf, prompt_ids, tokens, at, logits, steps) < LIMIT
    if threshold is None:  # the static schedule: n_s positions a step
        counts = np.bincount(at[B:], minlength=steps)  # the second block
        assert list(counts) == ref.schedule(B, steps)
    assert counters['forwards'] == 2 * (steps + 1)
    assert counters['decided'] == 2 * B - remainder and counters['blocks'] == 2
    assert counters['moe_pairs'] == (
        cfg.num_layers * cfg.experts_per_token * B * counters['forwards']
    )
    final = prompt_ids[:16] + [int(t) for t in tokens]
    _, kept = ref.forward(params, hf, final, keep=(0, 2), width=WIDTH)
    for layer in (0, 2):
        for pool, side in ((k, 0), (v, 1)):
            held = toy.held_pages(pool, row, layer, cfg)[:len(final)]
            assert ref.kv_content_error(held, kept[layer][side]) < 1e-5


def test_a_threshold_decides_more_than_the_schedule(model):
    hf, cfg, params = model
    prompt_ids = toy.prompt(np.random.default_rng(3), 16)
    _, static, logits, *_ = toy.paged_run(cfg, params, prompt_ids, 2)
    assert list(np.bincount(static[:B], minlength=B)) == [1, 1, 1, 1]
    # the reference's own confidences at the first block's first step; a
    # threshold just under the third of them
    first = logits[0, 0]
    conf = [ref.confidence(first[i], int(first[i].argmax())) for i in range(B)]
    tau = float(np.sort(conf)[-3]) * 0.999
    _, eager, *_ = toy.paged_run(cfg, params, prompt_ids, 2, threshold=tau)
    decided = ref.select(conf, np.ones(B, bool), 1, tau)
    assert decided.sum() == 3 and list(eager[:B] == 0) == list(decided)


# ------------------------------------------------------------- the two rules
@pytest.mark.parametrize('seed', range(6))
def test_select_unmask_is_its_numpy_twin(seed):
    rng = np.random.default_rng(seed)
    conf = rng.random((16, B)).astype(np.float32)
    conf[rng.random((16, B)) < 0.3] = 0.5  # ties: to the lower position
    masked = rng.random((16, B)) < 0.7
    for count in range(0, B + 1):
        for tau in (None, 0.4, 1.0):
            got = sampling.select_unmask(
                jnp.asarray(conf), jnp.asarray(masked), count,
                None if tau is None else jnp.full((16,), tau, jnp.float32),
            )
            want = [ref.select(c, m, count, tau) for c, m in zip(conf, masked)]
            assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize('temperature, top_p, top_k', [
    (0.0, 1.0, 0), (0.7, 1.0, 0), (0.7, 0.9, 0), (1.3, 0.6, 5), (0.5, 0.95, 0),
])
def test_confidence_is_the_kept_tokens_probability(temperature, top_p, top_k):
    """Under the FILTERED distribution for a sampled row, the argmax's
    softmax probability for a greedy one; ``sample_tokens`` draws the same
    tokens from the same keys."""
    rows = 24
    logits = jax.random.normal(jax.random.PRNGKey(1), (rows, 96)) * 3.0
    full = lambda x, dtype=jnp.float32: jnp.full((rows,), x, dtype)  # noqa: E731
    keys = sampling.fold_row_keys(
        jnp.arange(rows, dtype=jnp.uint32), jnp.arange(rows, dtype=jnp.int32)
    )
    tokens, conf = sampling.sample_tokens_confidence(
        logits, full(temperature), full(top_p), full(0.0),
        top_k=full(top_k, jnp.int32), row_keys=keys,
    )
    plain = sampling.sample_tokens(
        logits, None, full(temperature), full(top_p), full(0.0),
        top_k=full(top_k, jnp.int32), row_keys=keys,
    )
    assert np.array_equal(np.asarray(tokens), np.asarray(plain))
    want = [
        ref.confidence(np.asarray(logits[i]), int(tokens[i]), temperature,
                       top_k, top_p)
        for i in range(rows)
    ]
    assert np.abs(np.asarray(conf) - want).max() < 1e-5
    if temperature > 0 and (top_p < 1 or top_k):
        # the wrong program: confidence from the unfiltered distribution
        unfiltered = [
            ref.confidence(np.asarray(logits[i]), int(tokens[i]), temperature)
            for i in range(rows)
        ]
        assert np.abs(np.asarray(conf) - unfiltered).max() > 0.01


def test_expert_shares_add_up_to_the_uncut_layer(model):
    hf, cfg, params = model
    uncut_hf, uncut, whole = toy.tiny(0, num_experts=8, first_local_expert=0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, cfg.hidden_size))
    attn = jnp.zeros((1, 12, cfg.num_heads, cfg.head_dim))
    counted = jnp.ones((1, 12), bool)

    def layer_out(config, tree, first, held):
        lp = jax.tree.map(lambda a: a[0], {
            n: t for n, t in tree['layers'].items() if n not in sdar._BANKS
        })
        banks = {
            n: {'kernel': tree['layers'][n]['kernel'][:, first:first + held]}
            for n in sdar._BANKS
        }
        config = config.model_copy(update={
            'first_local_expert': first, 'num_local_experts': held,
        })
        out, pairs = sdar._finish_layer(x, attn, lp, banks, config, 0, counted)
        return out - x, pairs

    total, pairs = layer_out(uncut, whole, 0, 8)
    shares = [layer_out(uncut, whole, first, 4) for first in (0, 4)]
    assert spread(shares[0][0] + shares[1][0], total) < LIMIT
    assert int(pairs[0]) == int(pairs[1]) == 24
    assert int(shares[0][1][1]) + int(shares[1][1][1]) == 24


# --------------------------------------------------------- wrong programs
def _window_error(model, monkeypatch, patch=None, prefill_block=B):
    """``replay_error`` of a sampled window of three blocks behind a prefill,
    with ``patch`` applied to the program."""
    hf, cfg, params = model
    prompt_ids = toy.prompt(np.random.default_rng(5), 17)
    if patch is not None:
        patch(monkeypatch)
    wrong_prefill = sdar if prefill_block == B else _CausalPrefill(cfg)
    tokens, at, logits, *_ = toy.paged_run(
        cfg, params, prompt_ids, 3, sampling=(0.7, 0.9, 0),
        module=wrong_prefill,
    )
    return replay_error(params, hf, prompt_ids, tokens, at, logits, B)


class _CausalPrefill:
    """``sdar`` with its prefill under the causal mask."""

    def __init__(self, cfg):
        self.causal = cfg.model_copy(update={'block_length': 1})
        self.decode_loop = sdar.decode_loop

    def prefill_paged(self, params, cfg, *args, **kwargs):
        return sdar.prefill_paged(params, self.causal, *args, **kwargs)


def _skip_commit(monkeypatch):
    real, calls = sdar._block_pass, []

    def block_pass(params, cfg, rope, backend, ids, start, k, v, tables, live):
        calls.append(1)
        out = real(params, cfg, rope, backend, ids, start, k, v, tables, live)
        # traced twice a block: the denoise scan's body, then the commit
        return out if len(calls) % 2 else (out[0], k, v, out[3])

    monkeypatch.setattr(sdar, '_block_pass', block_pass)


def _router_in_bf16(monkeypatch):
    real = moe._rank

    def rank(x, router_kernel, *rest):
        return real(
            x.astype(jnp.bfloat16), router_kernel.astype(jnp.bfloat16), *rest
        )

    monkeypatch.setattr(moe, '_rank', rank)


@pytest.mark.parametrize('arm', [
    'right', 'causal_inside_a_block', 'commit_skipped', 'router_in_bf16',
])
def test_a_wrong_program_breaks_the_tolerance(model, monkeypatch, arm):
    patch = {'commit_skipped': _skip_commit, 'router_in_bf16': _router_in_bf16}
    error = _window_error(
        model, monkeypatch, patch.get(arm),
        prefill_block=1 if arm == 'causal_inside_a_block' else B,
    )
    if arm == 'right':
        assert error < LIMIT
    else:
        assert error > 10 * LIMIT


def test_keeping_the_lowest_confidence_is_not_the_references_rule(
    model, monkeypatch
):
    """Greedy, so that the reference knows every candidate: the positions a
    step decided are ``select`` over the reference's confidences; a program
    that keeps the LEAST confident is caught at the first step."""
    hf, cfg, params = model
    prompt_ids = toy.prompt(np.random.default_rng(6), 16)

    def first_step_agrees():
        _, at, logits, *_ = toy.paged_run(cfg, params, prompt_ids, 1)
        first = logits[0, 0]
        conf = [ref.confidence(first[i], int(first[i].argmax())) for i in range(B)]
        return list(at == 0) == list(ref.select(conf, np.ones(B, bool), 1))

    assert first_step_agrees()
    real = sampling.select_unmask
    monkeypatch.setattr(
        sampling, 'select_unmask',
        lambda conf, masked, count, tau=None: real(-conf, masked, count, tau),
    )
    assert not first_step_agrees()


# ------------------------------------------------------------ configuration
@pytest.mark.parametrize('key, value', [
    ('use_sliding_window', True), ('rope_scaling', {'type': 'yarn'}),
    ('mlp_only_layers', [0]), ('decoder_sparse_step', 2),
    ('attention_bias', True), ('norm_topk_prob', False),
    ('tie_word_embeddings', True), ('hidden_act', 'gelu'),
])
def test_from_hf_config_refuses_by_name_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=f'sdar_moe: {key}='):
        sdar.SdarConfig.from_hf_config(toy.tiny_hf(**{key: value}))


def test_the_published_keys_are_read_and_the_spec_declares_the_block():
    cfg = sdar.SdarConfig.from_hf_config(toy.tiny_hf())
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (8, 4, 2)
    spec = cfg.cache_spec()
    assert spec.block == B and not spec.dense_prefill
    assert spec.paged[0].num_layers == 3 and spec.programs.endswith('sdar')
    with pytest.raises(ValueError, match='whole blocks'):
        sdar.decode_loop(None, cfg, *[None] * 12, num_steps=6)


def test_params_from_hf_stacks_the_held_experts_or_refuses_by_name(model):
    hf, cfg, params = model
    state = {
        'model.embed_tokens.weight': np.asarray(params['embed']),
        'lm_head.weight': np.asarray(params['head']).T,
        'model.norm.weight': np.asarray(params['final_ln']['scale']),
    }
    names = {
        'attn_ln': 'input_layernorm', 'mlp_ln': 'post_attention_layernorm',
        'q_norm': 'self_attn.q_norm', 'k_norm': 'self_attn.k_norm',
    }
    layers = params['layers']
    for l in range(cfg.num_layers):
        at = f'model.layers.{l}'
        for ours, theirs in names.items():
            state[f'{at}.{theirs}.weight'] = np.asarray(layers[ours]['scale'][l])
        for n in ('q', 'k', 'v', 'o'):
            state[f'{at}.self_attn.{n}_proj.weight'] = np.asarray(layers[n]['kernel'][l]).T
        state[f'{at}.mlp.gate.weight'] = np.asarray(layers['router']['kernel'][l]).T
        for e in range(cfg.num_local_experts):
            for n in sdar._BANKS:
                state[f'{at}.mlp.experts.{cfg.first_local_expert + e}.{n}_proj.weight'] = (
                    np.asarray(layers[n]['kernel'][l, e]).T
                )
    loaded = sdar.params_from_hf(state, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    assert all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params))
    )
    del state['model.layers.1.mlp.experts.3.up_proj.weight']
    with pytest.raises(KeyError, match=r'layers\.1\.mlp\.experts\.3\.up_proj'):
        sdar.params_from_hf(state, cfg)
