"""The serving form of a family's parameter tree (PR 51): a family module may
hold some leaves a layer an array for its programs
(``deepseek_v3.serving_params``, ``common.unstack``, ``common.layer_at``),
the engine asks for that form once, and no number moves: the same arrays,
the same logits, the same tokens. What the form is FOR is in the compiled
text (``tests/test_aot_windows.py::test_decode_window_slices_no_weight``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepseek_toy import BLOCK, make_engine, paged_logits, prompt, tiny
from distllm_tpu.generate.engine.engine import SamplingParams, auto_layout_formats
from distllm_tpu.models import common, deepseek_v3

GREEDY = dict(temperature=0.0)
PER_LAYER = ('q', 'k_up', 'v_up')


def _fresh(layers=3, seed=0):
    """A seeded tree of this test's own (``tiny``'s is shared a process)."""
    _, cfg, _ = tiny(num_hidden_layers=layers)
    return cfg, deepseek_v3.init_on_device(jax.random.PRNGKey(seed), cfg)


def _shapes(tree):
    return jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)


def test_serving_form_is_the_stacks_own_layers():
    cfg, params = _fresh()
    public = _shapes(params)
    serving = deepseek_v3.serving_params(params)
    for name in PER_LAYER:
        stack, layers = params['attn'][name]['kernel'], serving['attn'][name]['kernel']
        assert isinstance(layers, tuple) and len(layers) == cfg.num_layers
        for i, layer in enumerate(layers):
            assert layer.shape == stack.shape[1:] and layer.dtype == stack.dtype
            np.testing.assert_array_equal(np.asarray(layer), np.asarray(stack[i]))
    # every other leaf is the public tree's own array, and that tree is as
    # it was: stacks, alive
    for kind in params:
        for name in params[kind] if isinstance(params[kind], dict) else ():
            if (kind, name) not in [('attn', n) for n in PER_LAYER]:
                assert serving[kind][name] is params[kind][name]
    assert serving['embed'] is params['embed']
    assert _shapes(params) == public
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(params))


@pytest.mark.parametrize('own', [False, True])
def test_the_stacks_go_only_when_the_caller_gives_them_up(own):
    _, params = _fresh()
    serving = deepseek_v3.serving_params(params, own=own)
    gone = {
        jax.tree_util.keystr(path) for path, leaf
        in jax.tree_util.tree_flatten_with_path(params)[0] if leaf.is_deleted()
    }
    assert gone == ({f"['attn']['{n}']['kernel']" for n in PER_LAYER} if own else set())
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(serving))


@pytest.mark.parametrize('tree', ['init_on_device', 'param_specs', 'params_from_hf'])
def test_the_public_tree_is_stacks(tree):
    """What the family hands out and takes in is what it was: one stacked
    leaf a parameter kind, the three of ``PER_LAYER`` among them."""
    cfg, params = _fresh()
    heads = cfg.num_heads
    want = {
        'q': (cfg.num_layers, cfg.hidden_size, heads * cfg.qk_head_dim),
        'k_up': (cfg.num_layers, cfg.kv_lora_rank, heads * cfg.qk_nope_head_dim),
        'v_up': (cfg.num_layers, cfg.kv_lora_rank, heads * cfg.v_head_dim),
    }
    if tree == 'init_on_device':
        assert {n: params['attn'][n]['kernel'].shape for n in want} == want
        assert len(jax.tree.leaves(params)) == 23
    elif tree == 'param_specs':
        specs = deepseek_v3.param_specs(cfg)
        is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)  # noqa: E731
        assert jax.tree.structure(specs, is_leaf=is_spec) == jax.tree.structure(params)
        assert all(
            len(specs['attn'][n]['kernel']) == len(shape) for n, shape in want.items()
        )
    else:
        with pytest.raises(NotImplementedError, match='no converter'):
            deepseek_v3.params_from_hf({}, cfg)


@pytest.mark.parametrize('leaf', ['device', 'host', 'traced'])
def test_unstack_takes_a_layer_at_a_time(leaf):
    stack = np.arange(5 * 3 * 4, dtype=np.float32).reshape(5, 3, 4)
    if leaf == 'device':
        layers = common.unstack(jnp.asarray(stack))
        assert all(isinstance(a, jax.Array) for a in layers)
    elif leaf == 'host':
        layers = common.unstack(stack)
        assert all(np.shares_memory(a, stack) for a in layers)
    else:  # the abstract tree the AOT compiles are lowered over
        layers = jax.eval_shape(common.unstack, jax.ShapeDtypeStruct(stack.shape, stack.dtype))
        assert [a.shape for a in layers] == [(3, 4)] * 5
        return
    assert len(layers) == 5
    for i, layer in enumerate(layers):
        np.testing.assert_array_equal(np.asarray(layer), stack[i])


def test_layer_at_picks_from_a_stack_and_from_a_tuple():
    stack = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4, 2, 3)
    tree = {
        'a': {'kernel': stack}, 'b': {'kernel': common.unstack(stack)},
        'bank': {'kernel': stack},
    }
    got = common.layer_at(tree, 2, skip=('bank',))
    assert sorted(got) == ['a', 'b']
    np.testing.assert_array_equal(got['a']['kernel'], stack[2])
    assert got['b']['kernel'] is tree['b']['kernel'][2]
    # a traced index (or ``dynamic=True``) slices a stack and cannot pick
    # from a tuple: a walk under a layer scan keeps its stacks
    for index, dynamic in ((jnp.int32(1), None), (1, True)):
        take = jax.jit(lambda t, i: common.layer_at(t, i, dynamic=dynamic), static_argnums=(
            () if dynamic is None else (1,)
        ))
        np.testing.assert_array_equal(take({'a': tree['a']}, index)['a']['kernel'], stack[1])
        with pytest.raises((TypeError, AttributeError)):
            take({'b': tree['b']}, index)


@pytest.mark.parametrize('dynamic', [None, True])
def test_layer_at_traces_a_stack_as_the_parents_did(dynamic):
    """The reader's new case is inert for a stacked leaf: the same jaxpr as
    the parent's ``layer_at`` (kept here), static and dynamic."""
    def parents(tree, i, skip=(), dynamic=None):
        if dynamic is None:
            dynamic = not isinstance(i, int)
        if dynamic:
            pick = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)  # noqa: E731
        else:
            pick = lambda a: a[i]  # noqa: E731
        return jax.tree.map(
            pick, {n: leaf for n, leaf in tree.items() if n not in skip}
        )

    tree = {
        'q': {'kernel': jnp.ones((4, 8, 16))}, 'ln': {'scale': jnp.ones((4, 8))},
        'bank': {'kernel': jnp.ones((4, 2, 8, 8))},
    }
    for index in (2, jnp.int32(2)):
        if isinstance(index, int):
            want = jax.make_jaxpr(lambda t: parents(t, index, ('bank',), dynamic))(tree)
            got = jax.make_jaxpr(lambda t: common.layer_at(t, index, ('bank',), dynamic))(tree)
        else:
            want = jax.make_jaxpr(lambda t, i: parents(t, i, ('bank',), dynamic))(tree, index)
            got = jax.make_jaxpr(lambda t, i: common.layer_at(t, i, ('bank',), dynamic))(tree, index)
        assert str(got) == str(want)


def test_paged_path_gives_the_same_logits_bit_for_bit():
    """``prefill_paged`` (three chunks) and the decode core over a dense
    layer and a sparse one: logits and planes from the stacked tree and from
    its serving form are the same bits."""
    _, cfg, params = tiny(num_hidden_layers=2)
    assert [cfg.mlp_of(li)[0] for li in range(2)] == ['dense', 'sparse']
    tokens = prompt(np.random.default_rng(5), 27)
    want, planes = paged_logits(cfg, params, tokens, 21)
    got, planes_ = paged_logits(cfg, deepseek_v3.serving_params(params), tokens, 21)
    np.testing.assert_array_equal(got, want)
    for a, b in zip(planes, planes_):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_loop_gives_the_same_tokens():
    _, cfg, params = tiny(num_hidden_layers=2)
    rows, width, steps = 3, 8, 5
    rng = np.random.default_rng(6)
    planes = tuple(
        jnp.asarray(rng.normal(size=(1 + rows * width, BLOCK, cfg.stored_row)), jnp.float32)
        for _ in range(cfg.num_layers)
    )
    table = jnp.asarray(1 + np.arange(rows * width, dtype=np.int32).reshape(rows, width))
    ctx = jnp.asarray([9, 17, 4], jnp.int32)
    ones, zeros = jnp.ones((rows,), jnp.float32), jnp.zeros((rows,), jnp.float32)

    def window(tree):
        return jax.jit(lambda tree, planes: deepseek_v3.decode_loop(
            tree, cfg, jnp.asarray([5, 6, 7]), ctx - 1, planes, (), table, ctx,
            jnp.asarray([steps, steps, 2]), zeros, ones, zeros,
            jnp.zeros((rows,), jnp.int32), jnp.arange(rows, dtype=jnp.uint32),
            num_steps=steps, max_table_positions=64,
        ))(tree, planes)

    want, got = window(params), window(deepseek_v3.serving_params(params))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_serves_the_form_and_generates_the_stacked_trees_tokens(monkeypatch):
    """An engine built over the public tree, as the benchmark's driver
    builds it, reads the serving form; its greedy tokens are those of an
    engine that was never offered one (the parent's programs over the
    stacks), which is also what a family without ``serving_params`` gets:
    the tree it gave, the same leaves."""
    rng = np.random.default_rng(7)
    prompts = [prompt(rng, n) for n in (21, 5, 30)]
    sampling = SamplingParams(max_tokens=9, **GREEDY)
    _, params, engine = make_engine()
    assert isinstance(engine.params['attn']['q']['kernel'], tuple)
    assert engine.params['attn']['o'] is params['attn']['o']
    assert not params['attn']['q']['kernel'].is_deleted()  # not the engine's
    got = engine.generate_ids(prompts, sampling)
    engine.shutdown()

    monkeypatch.delattr(deepseek_v3, 'serving_params')
    _, params, stacked = make_engine()
    assert stacked.params is params
    assert got == stacked.generate_ids(prompts, sampling)
    stacked.shutdown()


@pytest.mark.parametrize('toy', ['laguna_toy', 'lfm2_toy'])
def test_a_family_without_a_serving_form_is_served_from_its_tree(toy):
    import importlib

    module = importlib.import_module(toy)
    _, params, engine = module.make_engine()
    assert not hasattr(engine._programs, 'serving_params')
    assert engine.params is params
    engine.shutdown()


def test_auto_layout_and_migration_take_the_form_leaf_by_leaf(monkeypatch):
    """``auto_layout_formats`` asks a format a leaf (72 more of them at the
    cell's depth) and a round of ``_migrate_params`` hands the same tree
    back, the same bits, through one jitted identity a layout: a program a
    leaf was 71 more compiles and 5 s of the cell's set-up on the chip."""
    _, params = _fresh(layers=2)
    _, _, engine = make_engine(hf_over={'num_hidden_layers': 2})
    engine.params = deepseek_v3.serving_params(params, own=True)
    asked = auto_layout_formats(engine.params)
    assert jax.tree.structure(asked) == jax.tree.structure(engine.params)
    assert len(jax.tree.leaves(engine.params)) == 23 + 3 * (2 - 1)
    before = jax.tree.map(np.asarray, engine.params)
    formats = jax.tree.map(lambda a: a.format, engine.params)
    made, jit = [], jax.jit
    monkeypatch.setattr(jax, 'jit', lambda *a, **kw: made.append(1) or jit(*a, **kw))
    moved = engine._migrate_params(formats)
    monkeypatch.undo()
    assert len(made) == len({str(f.layout) for f in jax.tree.leaves(formats)})
    assert jax.tree.structure(moved) == jax.tree.structure(before)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(moved)):
        np.testing.assert_array_equal(a, np.asarray(b))
    engine.shutdown()
