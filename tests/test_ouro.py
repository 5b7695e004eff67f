"""``models/ouro.py`` at toy widths on the CPU (3 layers run 4 times, hidden
64, 4 heads of 16) against the plain reference ``benchmarks/reference_ouro.py``:
the dense forward, every pass's output and gate, the exit rule below the
published threshold, the paged path in prefill spans of two sizes and decode
steps, every plane ``t * L + l`` of the pool, the kernel at one query a KV
head, one pass against ``mistral`` with sandwich norms on the same weights,
the converter's names and the refusals. Float32 on both sides; no test here
judges a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ouro as ref
from distllm_tpu.models import decoder_family, mistral, ouro
from ouro_toy import paged_logits, prompt, reference_logits, spread, tiny, tiny_hf

TOLERANCE = 2e-4  # of the logits' spread: float32 both sides


def _rows(seed, sizes):
    rng = np.random.default_rng(seed)
    return [(prompt(rng, total), n) for total, n in sizes]


def test_the_family_is_served_from_its_config():
    config_cls, module = decoder_family('ouro')
    cfg = config_cls.from_hf_config(tiny_hf())
    assert module is ouro and cfg.post_norms and cfg.total_ut_steps == 4
    spec = cfg.cache_spec()
    assert spec.passes == 4 and spec.program_prefix == 'ouro_'
    assert [(g.name, g.num_layers, g.window) for g in spec.paged] == [('kv', 12, None)]
    assert spec.programs == 'distllm_tpu.models.ouro' and not spec.dense_prefill


@pytest.mark.parametrize('threshold', [0.3, 0.6, 1.0])
def test_dense_forward_and_the_exit_rule_are_the_references(threshold):
    """Logits, every pass's normed output, its gate, and per token the pass
    the head reads, with gate biases drawn wide so that tokens leave at
    every pass below the published threshold."""
    hf, cfg, params = tiny(0)
    cfg = cfg.model_copy(update={'early_exit_threshold': threshold})
    rng = np.random.default_rng(3)
    ids = np.asarray([prompt(rng, 19), prompt(rng, 19)], np.int32)
    hidden, passes = ouro.apply(
        params, cfg, jnp.asarray(ids), jnp.ones_like(ids), return_passes=True
    )
    want = ref.forward(
        params, hf, ids, np.tile(np.arange(19), (2, 1)), passes=True,
        threshold=threshold,
    )
    np.testing.assert_array_equal(passes['exit_pass'], want['exit_pass'])
    assert spread(ouro.logits(params, cfg, hidden), want['logits']) < TOLERANCE
    assert spread(passes['z'], want['z']) < TOLERANCE
    np.testing.assert_allclose(passes['gate'], want['gate'], atol=2e-4)
    if threshold == 1.0:  # the published threshold: the last pass
        assert (want['exit_pass'] == 3).all()
    else:  # tokens leave early, and not all at one pass
        assert len(np.unique(want['exit_pass'])) >= 3
    # without ``return_passes`` the same hidden state
    np.testing.assert_array_equal(
        ouro.apply(params, cfg, jnp.asarray(ids), jnp.ones_like(ids)), hidden
    )


def test_the_gate_chooses_a_hidden_state_that_tells():
    """The check is not blind: the head over another pass's output lies
    standard deviations away."""
    hf, cfg, params = tiny(0)
    tokens = prompt(np.random.default_rng(5), 12)
    low = reference_logits(params, hf, tokens, 0, threshold=0.3)
    last = reference_logits(params, hf, tokens, 0, threshold=1.0)
    assert spread(low, last) > 0.5


@pytest.mark.parametrize('chunk', [8, 5])
@pytest.mark.parametrize('threshold', [0.6, 1.0])
def test_paged_path_is_the_reference_in_logits_and_in_every_plane(chunk, threshold):
    """Prefill in spans (two splits), rows of unequal tails in one dispatch,
    then decode steps: the logits at every generated position, the counts
    of the pass each decoded token's head read, and all ``T * L`` planes of
    the pool against the reference's keys and values of that pass and layer."""
    hf, cfg, params = tiny(1)
    cfg = cfg.model_copy(update={'early_exit_threshold': threshold})
    rows = _rows(2, [(23, 14), (9, 3), (17, 17)])
    got, (k, v), tables, counts = paged_logits(cfg, params, rows, chunk=chunk)
    planes = range(cfg.num_planes)
    decoded = np.zeros(4, int)
    for row, (logits, (tokens, n)) in enumerate(zip(got, rows)):
        at = np.arange(n - 1, len(tokens))[None]
        want = ref.forward(
            params, hf, np.asarray(tokens)[None], at, planes=planes,
            threshold=threshold,
        )
        assert spread(logits, want['logits'][0]) < TOLERANCE
        decoded += np.bincount(want['exit_pass'][0, n:], minlength=4)
        for p in planes:
            for pool, rows_want in zip((k, v), want['planes'][p]):
                held = np.asarray(pool)[p][tables[row]].reshape(
                    -1, cfg.num_kv_heads, cfg.head_size
                )[:len(tokens)]
                assert ref.content_error(held, rows_want[0]) < 1e-5, p
    np.testing.assert_array_equal(counts, decoded)
    assert counts.sum() == sum(len(t) - n for t, n in rows)


def test_a_pass_never_reads_another_passes_plane():
    """Planes of other passes filled with noise before the walk change
    nothing: pass ``t`` writes its rows before it reads them and reads no
    other plane."""
    hf, cfg, params = tiny(1)
    rows = _rows(4, [(12, 8)])
    clean, (k, _), tables, _ = paged_logits(cfg, params, rows)
    # the shared-cache approximation (every pass on pass 0's planes) differs
    real = mistral._token_layer
    try:
        mistral._token_layer = lambda cfg_, rope, ab, row, carry, lp, plane, w, **kw: real(
            cfg_, rope, ab, row, carry, lp, plane % cfg_.num_layers, w, **kw
        )
        shared, _, _, _ = paged_logits(cfg, params, rows)
    finally:
        mistral._token_layer = real
    assert spread(shared[0][1:], clean[0][1:]) > 0.05
    assert np.abs(np.asarray(k)[3:]).max() > 0  # later passes hold rows


def test_one_query_a_kv_head_goes_through_the_kernel():
    """128-wide heads, as many KV heads as query heads (the published
    shape of a head group: one), through the Pallas interpreter in prefill
    spans and decode steps over the planes of four passes."""
    hf, cfg, params = tiny(
        2, hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
        head_dim=128, num_hidden_layers=2, layer_types=['full_attention'] * 2,
    )
    assert cfg.head_size == 128 and cfg.num_heads == cfg.num_kv_heads
    rows = _rows(3, [(11, 6), (5, 3)])
    got, _, _, _ = paged_logits(cfg, params, rows, backend='interpret')
    for logits, (tokens, n) in zip(got, rows):
        assert spread(logits, reference_logits(params, hf, tokens, n - 1)) < TOLERANCE


def test_one_pass_is_mistral_with_sandwich_norms_on_the_same_weights():
    """``total_ut_steps`` 1: the stack once, the final norm, the head, and
    a gate that has nothing to choose: ``mistral`` with ``post_norms`` over
    the same tree, in the dense forward and through the paged path."""
    hf, cfg, params = tiny(0, total_ut_steps=1)
    twin = mistral.MistralConfig(
        **{name: getattr(cfg, name) for name in mistral.MistralConfig.model_fields
           if name != 'name'}
    )
    assert twin.post_norms and cfg.cache_spec().passes == 1
    tree = {name: leaf for name, leaf in params.items() if name != 'exit_gate'}
    rng = np.random.default_rng(7)
    ids = jnp.asarray([prompt(rng, 15)], jnp.int32)
    np.testing.assert_array_equal(
        ouro.apply(params, cfg, ids, jnp.ones_like(ids)),
        mistral.apply(tree, twin, ids, jnp.ones_like(ids)),
    )
    rows = _rows(8, [(14, 9), (6, 2)])
    got, _, _, counts = paged_logits(cfg, params, rows)
    want, _, _, _ = paged_logits(twin, tree, rows, module=_MistralPrograms)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(counts, [9])


class _MistralPrograms:
    """``mistral``'s two serving functions under ``paged_logits``' names
    (its core returns no counts, its config declares no planes)."""

    prefill_paged = staticmethod(mistral.prefill_paged)

    @staticmethod
    def _decode_core(*args):
        logits, caches, _ = mistral._decode_core(*args)
        return logits, caches, jnp.zeros((1,), jnp.int32)


@pytest.mark.parametrize('key, value', [
    ('use_sliding_window', True),
    ('sliding_window', 4096),
    ('layer_types', ['full_attention', 'sliding_attention', 'full_attention']),
    ('rope_scaling', {'rope_type': 'yarn', 'factor': 4.0}),
    ('total_ut_steps', 0),
    ('tie_word_embeddings', True),
    ('attention_bias', True),
    ('hidden_act', 'gelu'),
])
def test_from_hf_config_refuses_by_name(key, value):
    with pytest.raises(ValueError, match=f'ouro: {key}=.* is not implemented'):
        ouro.OuroConfig.from_hf_config(tiny_hf(**{key: value}))


def _published_state(params, cfg) -> dict:
    """``params`` under the published tensor names (torch ``Linear`` weights
    are ``[out, in]``)."""
    layers, state = params['layers'], {}
    names = {
        'q': 'self_attn.q_proj', 'k': 'self_attn.k_proj', 'v': 'self_attn.v_proj',
        'o': 'self_attn.o_proj', 'gate': 'mlp.gate_proj', 'up': 'mlp.up_proj',
        'down': 'mlp.down_proj',
    }
    norms = {
        'attn_ln': 'input_layernorm', 'post_attn_ln': 'input_layernorm_2',
        'mlp_ln': 'post_attention_layernorm',
        'post_mlp_ln': 'post_attention_layernorm_2',
    }
    for i in range(cfg.num_layers):
        for ours, theirs in names.items():
            state[f'model.layers.{i}.{theirs}.weight'] = np.asarray(
                layers[ours]['kernel'][i]
            ).T
        for ours, theirs in norms.items():
            state[f'model.layers.{i}.{theirs}.weight'] = np.asarray(
                layers[ours]['scale'][i]
            )
    state['model.embed_tokens.weight'] = np.asarray(params['embed'])
    state['model.norm.weight'] = np.asarray(params['final_ln']['scale'])
    state['lm_head.weight'] = np.asarray(params['lm_head']).T
    state['model.early_exit_gate.weight'] = np.asarray(params['exit_gate']['kernel']).T
    state['model.early_exit_gate.bias'] = np.asarray(params['exit_gate']['bias'])
    return state


def test_params_from_hf_reads_the_published_names():
    hf, cfg, params = tiny(0)
    state = _published_state(params, cfg)
    got = ouro.params_from_hf(state, cfg)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    for gone in ('model.layers.1.input_layernorm_2.weight',
                 'model.early_exit_gate.weight'):
        short = {k: v for k, v in state.items() if k != gone}
        name = gone.removeprefix('model.')
        with pytest.raises(ValueError, match=f"ouro: the checkpoint has no '{name}'"):
            ouro.params_from_hf(short, cfg)


def test_seeded_trees_have_the_gate_and_the_specs_name_every_leaf():
    hf, cfg, _ = tiny(0)
    on_device = ouro.init_on_device(jax.random.PRNGKey(3), cfg)
    host = ouro.init(jax.random.PRNGKey(3), cfg)
    assert jax.tree.structure(on_device) == jax.tree.structure(host)
    assert on_device['exit_gate']['kernel'].shape == (64, 1)
    assert not np.asarray(on_device['exit_gate']['bias']).any()
    assert {'post_attn_ln', 'post_mlp_ln'} <= set(on_device['layers'])
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    specs = ouro.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=is_spec) == jax.tree.structure(on_device)


def test_the_cost_model_counts_the_stack_once_a_pass():
    from distllm_tpu.observability.roofline import CostModel

    hf, cfg, params = tiny(0)
    once = CostModel.from_params(params, 4)
    looped = CostModel.from_params(params, 4, layer_passes=cfg.total_ut_steps)
    stack = sum(leaf.size for leaf in jax.tree.leaves(params['layers']))
    assert looped.n_params - once.n_params == 3 * stack
    assert looped.weight_bytes - once.weight_bytes == 3 * stack * 4
