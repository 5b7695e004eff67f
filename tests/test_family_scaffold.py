"""What the served families share and no longer write themselves (PR 44):
the seeded weights' bits, the decode window's step scan
(``models.common.decode_window``) and the decode row's way into the kernel
(``ops.paged_attention.decode_attention``). Small shapes, under a second a
case; no test here judges a time."""

import hashlib

import deepseek_toy
import falcon_h1_toy
import granite_toy
import jax
import jax.numpy as jnp
import laguna_toy
import lfm2_toy
import numpy as np
import ouro_toy
import pytest
import sdar_toy
import smallthinker_toy
import solar_open2_toy

from distllm_tpu.models import common, decoder_families, decoder_family, mistral
from distllm_tpu.ops.paged_attention import (
    decode_attention,
    paged_attention_xla,
    ragged_paged_attention_xla,
)

# ---------------------------------------------------------- the weights' bits
# sha256 over every leaf of ``init_on_device(PRNGKey(7), cfg)`` at the
# family's toy widths in bfloat16 (path, dtype, shape, bytes; the leaves in
# the tree's own order), taken on the PARENT of PR 44 (commit fbc1eca): the
# benchmark's cells take their weights from these functions
# (``benchmarks/drivers``), so a builder that moves a fold-in number, the
# sorted leaf order or a dtype moves every cell's numbers and its check's
# limits. A change here is a change of the benchmark's weights: say so.
_PARENT_BITS = {
    'mistral': 'a46105dac73a068c35f7df089517ae05d75885ad93d3882b37e7eeb1a0ad54bc',
    'granite': 'ac10ec6faf04689832d477db20cc03ab8ccb8f0921f154a13dc4993ae564905d',
    'laguna': '87a49ecddf5a850869f342c39b8796cc8ac714d1147408bfcb728eef797901eb',
    'deepseek_v3': 'feb99441d81cdc633c057ae49aa218172b90b535bf537d16b766c8e2feac9574',
    'lfm2': '0796b9df8c5661c7868a8657a9ef349aea6d1cba60a250ebba576fecdc866252',
    'falcon_h1': '09463b44e0d3bfd98c5429e853b43db2cd05476ead9b63cc027dab2aca85c83d',
    # taken where the family was written (PR 45), not on PR 44's parent
    'solar_open2': 'da55a43a7e7f04fc0fc8014976cbdee6c6b7d2fc4ab6e8fe199087e2fdd52fef',
    # likewise (PR 48): ``mistral.init_on_device``'s tree and the exit gate
    'ouro': '36c7c9240d95edc82dbf050dad7277afc3eff4524b7aaed06783d1a13fbe8fd3',
    # likewise (PR 52): two attention trees and the expert tree of every layer
    'smallthinker': '0bf2f299e74957cf2880c00ce10b7d639da3f6878242ae2e8c5a26fafe22a4fa',
    # likewise (PR 54): one tree of layers with the heads' q and k norms
    'sdar': 'a61c0217f4e6d51eef47e085f5cd5ec12d6418f771a07af2a2a6c45110227a6e',
}
_TOYS = {
    'granite': granite_toy, 'laguna': laguna_toy, 'deepseek_v3': deepseek_toy,
    'lfm2': lfm2_toy, 'falcon_h1': falcon_h1_toy,
    'solar_open2': solar_open2_toy, 'ouro': ouro_toy,
    'smallthinker': smallthinker_toy, 'sdar': sdar_toy,
}


def _toy(family):
    if family == 'mistral':
        return mistral, mistral.MistralConfig(
            vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, intermediate_size=96, dtype='bfloat16',
        )
    hf = _TOYS[family].tiny_hf()
    config_cls, module = decoder_family(hf['model_type'])
    return module, config_cls.from_hf_config(hf)


@pytest.mark.parametrize('family', sorted(_PARENT_BITS))
def test_seeded_weights_are_the_parents_bits(family):
    module, cfg = _toy(family)
    assert cfg.dtype == 'bfloat16'
    params = module.init_on_device(jax.random.PRNGKey(7), cfg)
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        leaf = np.asarray(leaf)
        digest.update(
            f'{jax.tree_util.keystr(path)} {leaf.dtype} {leaf.shape}\n'.encode()
        )
        digest.update(leaf.tobytes())
    assert digest.hexdigest() == _PARENT_BITS[family]
    # and the specs name every leaf of the tree, and nothing else
    specs = module.param_specs(cfg)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    assert jax.tree.structure(specs, is_leaf=is_spec) == jax.tree.structure(params)


@pytest.mark.parametrize('family', [
    name for name, toy in _TOYS.items()
    if 'unnamed' in getattr(toy, 'ENGINE_CASES', ())
])
def test_no_line_of_the_engine_names_the_family(family):
    from pathlib import Path

    import distllm_tpu.generate.engine as engine_package

    words = _TOYS[family].ENGINE_CASES['unnamed']
    for path in Path(engine_package.__file__).parent.glob('*.py'):
        text = path.read_text().lower()
        assert not any(word in text for word in words), path.name


# ------------------------------------------------------- one form of pool
@pytest.mark.parametrize('model_type', sorted(decoder_families()))
def test_every_kv_group_is_one_stacked_pool(model_type):
    """Whatever a family's ``cache_spec()`` declares, each K/V group's
    pool is ONE array a plane over the group's layers, ``[L, blocks,
    block_size, row]`` (a ``QuantizedKV`` of such data and its scales for an
    int8 pool): the form the writers, the kernel, the mesh and the int8
    container are written for. The next family is held to it here. A latent
    group's is a plane a layer and no V plane (``models/deepseek_v3.py``)."""
    from distllm_tpu.generate.engine.kv_cache import PagedKVCache
    from distllm_tpu.ops.paged_attention import QuantizedKV

    config_cls, _ = decoder_family(model_type)
    toys = {toy.tiny_hf()['model_type']: toy for toy in _TOYS.values()}
    cfg = (
        config_cls.from_hf_config(toys[model_type].tiny_hf())
        if model_type in toys else config_cls()
    )
    spec = cfg.cache_spec()
    assert spec.paged
    for group in spec.paged:
        if spec.latent:
            kv = PagedKVCache.for_group(group, cfg, 8, 4, lazy=True)
            assert kv.spec('v') == () and [p.shape for p in kv.spec('k')] == [
                (8, 4, group.stored_row)
            ] * group.num_layers
            continue
        for dtype in ('bfloat16', 'int8'):
            # the engine's own way to a group's pool
            kv = PagedKVCache.for_group(group, cfg, 8, 4, dtype=dtype, lazy=True)
            for plane in (kv.spec('k'), kv.spec('v')):
                if dtype == 'int8':
                    assert isinstance(plane, QuantizedKV)
                    assert plane.scale.shape == (group.num_layers, 8, cfg.num_kv_heads)
                    plane = plane.data
                assert isinstance(plane, jax.ShapeDtypeStruct)
                assert plane.shape == (group.num_layers, 8, 4, plane.shape[-1])


# ------------------------------------------------------------ the step scan
_VOCAB = 32


def _window(counts, tables, steps_left, num_steps=4):
    """``decode_window`` over a core whose logits peak at ``ids + 1`` (greedy
    rows) and whose cache, a dict, adds up what each step was given: the
    live rows' ids, every row's position and context, and column 1 of its
    block table(s). Rows start at ids 3.., positions 10.., contexts 11..."""
    b = len(steps_left)

    def core(ids, pos, ctx, caches, tables, live):
        (seen,) = caches
        column = sum(table[:, 1] for table in jax.tree.leaves(tables))
        seen = {
            'ids': seen['ids'] + jnp.where(live, ids, 0),
            'pos': seen['pos'] + pos, 'ctx': seen['ctx'] + ctx,
            'table': seen['table'] + column,
        }
        logits = jax.nn.one_hot((ids + 1) % _VOCAB, _VOCAB) * 10.0
        step = jax.tree.map(
            lambda zero: jnp.full_like(zero, jnp.sum(live, dtype=zero.dtype)),
            counts,
        )
        return logits, (seen,), step

    rows = jnp.arange(b, dtype=jnp.int32)
    full = lambda value, dtype: jnp.full((b,), value, dtype)  # noqa: E731
    zeros = {name: jnp.zeros((b,), jnp.int32) for name in ('ids', 'pos', 'ctx', 'table')}
    tokens, (seen,), last_ids, summed = common.decode_window(
        core, rows + 3, rows + 10, rows + 11, (zeros,), tables,
        jnp.asarray(steps_left, jnp.int32), full(0.0, jnp.float32),
        full(1.0, jnp.float32), full(0.0, jnp.float32), full(0, jnp.int32),
        full(5, jnp.uint32), num_steps=num_steps, sampling_top_window=0,
        counts=counts,
    )
    return np.asarray(tokens), jax.tree.map(np.asarray, seen), np.asarray(last_ids), summed


@pytest.mark.parametrize('counts', [
    jnp.zeros((2,), jnp.int32),
    jnp.zeros((), jnp.int32),
    {'rows': jnp.zeros((), jnp.int32), 'pairs': jnp.zeros((2,), jnp.int32)},
    (),
], ids=['array', 'scalar', 'dict', 'none'])
def test_decode_window_counts_only_live_steps(counts):
    """Rows with 0, 2 and 9 steps left in a window of 4: a dead row keeps
    its id, a row that runs out stops there, and the counter, whatever its
    shape, sums the live rows of the steps that ran: 0 + 2 + 4."""
    table = jnp.arange(3 * 5, dtype=jnp.int32).reshape(3, 5) + 1
    tokens, seen, last_ids, summed = _window(counts, table, [0, 2, 9])
    np.testing.assert_array_equal(last_ids, [3, 4 + 2, 5 + 4])
    np.testing.assert_array_equal(tokens[:, 2], [6, 7, 8, 9])
    np.testing.assert_array_equal(tokens[:2, 1], [5, 6])  # then garbage
    # the live rows' ids alone: 4 + 5, and 5 + 6 + 7 + 8
    np.testing.assert_array_equal(seen['ids'], [0, 9, 26])
    for leaf in jax.tree.leaves(summed):
        np.testing.assert_array_equal(np.asarray(leaf), np.full(leaf.shape, 6))
    assert jax.tree.structure(summed) == jax.tree.structure(counts)


@pytest.mark.parametrize('groups', [1, 2], ids=['one_table', 'two_tables'])
def test_decode_window_sends_a_dead_row_to_the_trash_block(groups):
    """What ``core`` is given a step: a row out of budget sees block 0 in
    every cache group's table, and its position and context stay."""
    one = jnp.arange(3 * 5, dtype=jnp.int32).reshape(3, 5) + 1
    tables = one if groups == 1 else (one, one + 100)
    _, seen, _, _ = _window(jnp.zeros((2,), jnp.int32), tables, [0, 2, 9])
    # positions 10, 11, 12: the dead row's four times over, the second
    # row's 11 + 12 and then 13 twice, the third's 12 + 13 + 14 + 15
    np.testing.assert_array_equal(seen['pos'], [40, 49, 54])
    np.testing.assert_array_equal(seen['ctx'], [44, 53, 58])
    # column 1 of the table(s), once a LIVE step: rows 2, 7, 12 of ``one``
    column = np.asarray(one[:, 1]) * groups + 100 * (groups - 1)
    np.testing.assert_array_equal(seen['table'], column * [0, 2, 4])


# ------------------------------------------------- a decode row's way in
def _pools(rng, *, layers=None, row=16, blocks=12, block=4):
    shape = (blocks, block, row) if layers is None else (layers, blocks, block, row)
    return (
        jnp.asarray(rng.standard_normal(shape), jnp.float32),
        jnp.asarray(rng.standard_normal(shape), jnp.float32),
    )


@pytest.mark.parametrize('backend', ['xla', 'interpret'])
@pytest.mark.parametrize('case', ['stacked_pool', 'latent_plane', 'window'])
def test_decode_attention_is_both_twins_at_a_span_of_one(case, backend):
    """Against ``paged_attention_xla`` (what it is under ``'xla'``) and
    against ``ragged_paged_attention_xla`` at a span of one, with the
    operands the families pass: a stacked pool and its layer, a latent
    plane and its value lanes, a sliding window."""
    rng = np.random.default_rng(11)
    tables = jnp.asarray(rng.permutation(11)[:10].reshape(2, 5) + 1, jnp.int32)
    ctx = jnp.asarray([19, 6], jnp.int32)
    kw = {}
    if case == 'stacked_pool':
        k, v = _pools(rng, layers=3)
        q = jnp.asarray(rng.standard_normal((2, 4, 8)), jnp.float32)
        kw = dict(layer=2, scale=0.3)
    elif case == 'latent_plane':
        k, v = _pools(rng, row=256)[0], None
        q = jnp.asarray(rng.standard_normal((2, 8, 256)), jnp.float32)
        kw = dict(value_lanes=128, scale=0.2)
    else:
        k, v = _pools(rng)
        q = jnp.asarray(rng.standard_normal((2, 4, 8)), jnp.float32)
        kw = dict(sliding_window=5, logit_softcap=20.0)
    got = decode_attention(q, k, v, tables, ctx, ctx - 1, backend=backend, **kw)
    want = paged_attention_xla(q, k, v, tables, ctx, **kw)
    span = ragged_paged_attention_xla(
        q[:, None], k, v, tables, ctx, (ctx - 1)[:, None], **kw
    )[:, 0]
    assert got.shape == want.shape == (2, q.shape[1], kw.get('value_lanes', 8))
    if backend == 'xla':
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, span, rtol=2e-5, atol=2e-5)


def test_decode_attention_refuses_an_unresolved_backend():
    rng = np.random.default_rng(12)
    k, v = _pools(rng)
    q = jnp.zeros((1, 4, 8))
    args = (jnp.ones((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="unresolved or unknown attn backend 'auto'"):
        decode_attention(q, k, v, *args, backend='auto')
