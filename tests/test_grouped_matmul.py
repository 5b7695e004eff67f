"""The grouped form's Pallas kernel (``ops/grouped_matmul.py``) against
``jax.lax.ragged_dot`` in interpret mode at tiny shapes, ``routed_experts``
with the kernel forced on against its ``ragged_dot`` path at each family's
arguments, and the tile rule as a table of the four cells' program shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distllm_tpu.models import moe
from distllm_tpu.ops import grouped_matmul
from test_moe_forms import H, I, LAYERS, _inputs

K, N, TILE = 64, 24, 16


def _banks(rng, dtype, held):
    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.2, dtype)

    return (
        normal(LAYERS, held, K, N), normal(LAYERS, held, K, N),
        normal(LAYERS, held, N, K),
    )


def _reference(rows, gate, up, down, sizes):
    hidden = jax.nn.silu(
        jax.lax.ragged_dot(rows, gate, sizes)
    ) * jax.lax.ragged_dot(rows, up, sizes)
    return jax.lax.ragged_dot(hidden, down, sizes)


# (id, rows, one layer's group sizes, the layer and how it is given)
KERNEL_CASES = [
    ('empty_group_between_two_full', 64, (32, 0, 32), ('static', 0)),
    ('group_straddles_a_tile', 64, (5, 30, 9, 20), ('static', 1)),
    ('three_groups_in_one_tile', 32, (3, 4, 5), ('static', 0)),
    ('rows_past_the_last_group', 96, (20, 0, 37, 9), ('static', 2)),
    ('whole_tiles_past_the_last_group', 128, (7, 12), ('static', 0)),
    ('no_pair_held', 32, (0, 0, 0), ('static', 1)),
    ('first_groups_empty', 48, (0, 0, 17, 31), ('static', 0)),
    ('traced_layer_of_three', 80, (16, 1, 0, 40), ('traced', 2)),
    ('traced_layer_zero', 80, (11, 22, 33), ('traced', 0)),
]


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize(
    'rows, sizes, layer', [c[1:] for c in KERNEL_CASES],
    ids=[c[0] for c in KERNEL_CASES],
)
def test_kernel_is_ragged_dot(dtype, rows, sizes, layer):
    """The rows of every group equal ``ragged_dot``'s three calls over one
    layer's bank (float32 to its last bits; bfloat16 within two ulps of the
    largest value: the CPU's ``silu`` rounds each step to bfloat16 where
    the kernel, as the TPU's fusion, rounds its product once); rows past
    the last group are whatever they were."""
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(len(sizes) * rows)
    gate, up, down = _banks(rng, dtype, len(sizes))
    x = jnp.asarray(rng.normal(size=(rows, K)), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    how, index = layer
    flat = [w.reshape(-1, *w.shape[2:]) for w in (gate, up, down)]
    tiles = (TILE, N, K)
    if how == 'traced':
        got = jax.jit(
            lambda x, li: grouped_matmul.expert_matmuls(
                x, *flat, sizes, li, tiles=tiles, interpret=True
            )
        )(x, jnp.int32(index))
    else:
        got = grouped_matmul.expert_matmuls(
            x, *flat, sizes, index, tiles=tiles, interpret=True
        )
    want = _reference(x, gate[index], up[index], down[index], sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    held = int(sizes.sum())
    got, want = (np.asarray(a[:held], np.float32) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        assert np.abs(got - want).max(initial=0.0) <= 2.0 ** -6 * max(
            1.0, np.abs(want).max(initial=0.0)
        )


def test_kernel_refuses_rows_that_are_not_whole_tiles():
    rng = np.random.default_rng(0)
    gate, up, down = (w[0] for w in _banks(rng, jnp.float32, 2))
    with pytest.raises(ValueError, match='whole'):
        grouped_matmul.expert_matmuls(
            jnp.zeros((40, K)), gate, up, down, jnp.asarray([8, 8], jnp.int32),
            0, tiles=(TILE, N, K), interpret=True,
        )


def test_column_tiles_cover_the_width():
    """Two column tiles a bank (the grid's first axis) give what one does."""
    rng = np.random.default_rng(3)
    wide = 256
    x = jnp.asarray(rng.normal(size=(32, wide)), jnp.float32)
    gate, up = (
        jnp.asarray(rng.normal(size=(2, wide, wide)) * 0.1, jnp.float32)
        for _ in range(2)
    )
    down = jnp.asarray(rng.normal(size=(2, wide, wide)) * 0.1, jnp.float32)
    sizes = jnp.asarray([13, 15], jnp.int32)
    one, two = (
        grouped_matmul.expert_matmuls(
            x, gate, up, down, sizes, 0, tiles=(TILE, columns, columns),
            interpret=True,
        )[:28]
        for columns in (wide, wide // 2)
    )
    np.testing.assert_allclose(
        np.asarray(one), np.asarray(two), rtol=1e-5, atol=1e-5
    )


# ---- routed_experts with the kernel forced on ----

# (id, tokens, k, keyword arguments, the traced layer or None): the four
# families' routers, and a token count whose pairs need the pad (13 x 3).
FAMILY_CASES = [
    ('granite_softmax', 32, 3, dict(first_expert=4), 1),
    ('laguna_softmax_scaled', 32, 2, dict(routed_scale=2.5, first_expert=8), 2),
    ('kanana_sigmoid_bias_scaled', 48, 2, dict(
        scoring='sigmoid', bias=True, routed_scale=2.448, first_expert=12), 0),
    ('lfm2_sigmoid_bias_eps', 32, 3, dict(
        scoring='sigmoid', bias=True, norm_eps=1e-6), 2),
    ('rows_that_need_the_pad', 13, 3, dict(), 1),
    ('one_layer_no_stack', 24, 3, dict(first_expert=4), None),
]


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize(
    'tokens, k, kw, layer', [c[1:] for c in FAMILY_CASES],
    ids=[c[0] for c in FAMILY_CASES],
)
def test_routed_experts_on_the_kernel(monkeypatch, dtype, tokens, k, kw, layer):
    """The grouped form over the kernel against the grouped form over
    ``ragged_dot``: same pairs, outputs apart by the rounding of a
    token's k pairs."""
    kw = dict(kw)
    data = _inputs(jnp.dtype(dtype), tokens)
    if kw.pop('bias', False):
        kw['select_bias'] = data['bias']
    banks = [data[n] for n in ('gate', 'up', 'down')]
    monkeypatch.setattr(moe, 'expert_form', lambda *shape: 'grouped')
    monkeypatch.setattr(  # the toy's pairs in several tiles
        grouped_matmul, 'grouped_tiles', lambda *shape: (TILE, I, H)
    )

    def call():
        if layer is None:
            return moe.routed_experts(
                data['x'], data['router'], *(b[0] for b in banks), k, **kw
            )
        return jax.jit(
            lambda x, li: moe.routed_experts(
                x, data['router'], *banks, k, layer=li, **kw
            )
        )(data['x'], jnp.int32(layer))

    out = {}
    for backend in ('xla', 'interpret'):
        monkeypatch.setattr(moe, 'grouped_backend', lambda b=backend: b)
        assert (moe.grouped_tiles(tokens, k, H, I) is None) == (
            backend == 'xla'
        )
        out[backend] = jax.tree.map(np.asarray, call())
    (want, want_pairs), (got, got_pairs) = out['xla'], out['interpret']
    assert got.dtype == want.dtype and got.shape == (tokens, H)
    np.testing.assert_array_equal(got_pairs, want_pairs)
    assert 0 < int(got_pairs[1]) <= int(got_pairs[0])
    want, got = want.astype(np.float32), got.astype(np.float32)
    ulp = 2.0 ** -8 if dtype == 'bfloat16' else 2.0 ** -20
    assert np.abs(got - want).max() <= 2 * k * ulp * max(
        1.0, np.abs(want).max()
    )
    assert np.abs(want).max() > 0.01


# ---- the way back: one gather of a token's k rows, gated and summed ----

# (id, tokens, k, the share of pairs held)
COMBINE_CASES = [
    ('k_of_2', 32, 2, 0.5),
    ('k_of_3', 24, 3, 0.5),
    ('k_of_10', 16, 10, 0.5),
    ('a_quarter_held', 40, 6, 0.25),
    ('every_pair_held', 16, 4, 1.0),
    ('rows_that_need_the_pad', 13, 3, 0.6),
]


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize(
    'tokens, k, share', [c[1:] for c in COMBINE_CASES],
    ids=[c[0] for c in COMBINE_CASES],
)
def test_combine_is_the_three_passes(dtype, tokens, k, share):
    """``moe.combine`` (PR 43: the k rows of a token gathered in the rows'
    dtype, gated and summed in float32 behind the gather) against the three
    float32 ``[pairs, H]`` passes it replaced (PR 42's lines: the weighted
    product in sorted order, its gather back to token order, the sum over
    k), to the last bit: the same products in the same order. A token with
    no held pair reads exact zeros whatever the rows past the last group
    hold (NaN here, and in the pad rows); one whose k pairs are all held is
    in every case."""
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(int(tokens * k + 100 * share))
    pairs = tokens * k
    held = rng.random((tokens, k)) < share
    held[1], held[2] = False, True
    # the sorted order: held pairs first (as if by expert), the rest behind
    flat = held.reshape(-1)
    order = np.concatenate([
        rng.permutation(np.nonzero(flat)[0]), np.nonzero(~flat)[0]
    ])
    rows = rng.normal(size=(pairs + 5, H)).astype(np.float32)
    rows[flat.sum():] = np.nan  # never computed
    rows = jnp.asarray(rows, dtype)
    weights = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    place = jnp.where(
        jnp.asarray(held), jnp.argsort(jnp.asarray(order)).reshape(tokens, k),
        -1,
    )
    got = moe.combine(rows, place, weights)
    parent = jnp.where(
        jnp.asarray(flat)[order][:, None],
        rows[:pairs].astype(jnp.float32)
        * weights.reshape(-1)[order][:, None],
        0.0,
    )[jnp.argsort(jnp.asarray(order))].reshape(tokens, k, -1).sum(axis=1)
    assert got.shape == (tokens, H) and got.dtype == jnp.float32
    got, parent = np.asarray(got), np.asarray(parent)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[1], 0.0)
    assert np.abs(got[2]).min() > 0.0
    np.testing.assert_array_equal(got, parent)


def test_grouped_form_makes_no_float32_pairs(monkeypatch):
    """The lowered grouped form holds no float32 tensor of ``[pairs, H]``
    on either backend (the ``ragged_dot`` one here; the kernel's at the
    cells' widths in ``tests/test_aot_tpu.py``): what is float32 is ``[T,
    k, H]`` inside the fusion behind the gather, and ``[T, H]``."""
    data = _inputs(jnp.bfloat16, 160)
    monkeypatch.setattr(moe, 'expert_form', lambda *shape: 'grouped')
    text = jax.jit(
        lambda x: moe.routed_experts(
            x, data['router'], data['gate'][0], data['up'][0],
            data['down'][0], 3,
        )
    ).lower(data['x']).as_text()
    assert f'tensor<480x{H}xbf16>' in text  # the rows, in sorted order
    assert f'tensor<480x{H}xf32>' not in text
    assert f'tensor<160x3x{H}xf32>' in text


def test_other_backends_keep_ragged_dot(monkeypatch):
    """The tests' backend is the CPU: no tiles, and no kernel call in the
    lowered text."""
    assert moe.grouped_backend() == 'xla'
    assert moe.grouped_tiles(2048, 4, 2048, 1792) is None
    data = _inputs(jnp.float32, 160)
    text = jax.jit(
        lambda x: moe.routed_experts(
            x, data['router'], data['gate'][0], data['up'][0],
            data['down'][0], 3,
        )
    ).lower(data['x']).as_text()
    assert 'grouped_matmul' not in text and 'custom_call' not in text
    monkeypatch.setattr(moe, 'grouped_backend', lambda: 'interpret')
    assert moe.grouped_tiles(2048, 4, 2048, 1792) is not None


# ---- the tile rule over the four cells' program shapes ----

# family: (k, E_held, E_routed, H, I); the rows an expert expects at the
# (512, 4) program are tokens * k / E_routed: 284, 256, 96, 64.
CELLS = {
    'granite': (10, 36, 72, 4096, 768),
    'lfm2': (4, 16, 32, 2048, 1792),
    'kanana': (6, 32, 128, 2048, 768),
    'laguna': (8, 64, 256, 2048, 512),
}
# the cells' grouped programs: (512, 4), (512, 1), granite's (128, 1) tail
PROGRAMS = [
    ('granite', 2048), ('granite', 512), ('granite', 128), ('lfm2', 2048),
    ('lfm2', 512), ('kanana', 2048), ('kanana', 512), ('laguna', 2048),
    ('laguna', 512),
]


@pytest.mark.parametrize('family, tokens', PROGRAMS)
def test_tile_rule_over_the_cells_programs(monkeypatch, family, tokens):
    k, held, routed, hidden, width = CELLS[family]
    monkeypatch.setattr(moe, 'grouped_backend', lambda: 'pallas')
    assert moe.expert_form(tokens, k, held, routed, hidden, width) == 'grouped'
    tiles = moe.grouped_tiles(tokens, k, hidden, width)
    # 128 rows a step, every bank tile the whole kernel: read once a call
    assert tiles == (128, width, hidden)
    row_tile, up_columns, down_columns = tiles
    # the call's pairs are whole row tiles with no pad at these shapes and
    # the blocks fit the budget
    assert tokens * k % row_tile == 0
    assert grouped_matmul._block_bytes(
        row_tile, hidden, up_columns, 2, 2
    ) <= grouped_matmul.VMEM_BLOCK_BYTES
    assert grouped_matmul._block_bytes(
        row_tile, width, down_columns, 1, 2
    ) <= grouped_matmul.VMEM_BLOCK_BYTES


def test_tile_rule_is_a_function_of_the_shapes():
    """A bank tile that does not fit is halved in its columns, the
    contraction is never cut; a call of a few rows takes them in whole
    sublane tiles."""
    assert grouped_matmul.grouped_tiles(8192, 8192, 4096) == (128, 512, 2048)
    assert grouped_matmul.grouped_tiles(39, 64, 24) == (48, 24, 64)
    assert grouped_matmul.grouped_tiles(24, 64, 24, itemsize=4) == (32, 24, 64)
