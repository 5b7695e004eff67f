"""The routed experts' grouped matmuls as one Pallas TPU kernel.

``expert_matmuls`` is what ``models/moe.py``'s grouped form runs on a TPU
where ``jax.lax.ragged_dot`` stood three times: rows sorted by expert are
multiplied by their own expert's ``gate`` and ``up`` kernels in one pass
(``silu(g) * u``, or ``relu(g) * u``), then by its ``down`` kernel. XLA's
grouped matmul behind ``ragged_dot`` ran at a fifth of the chip's bf16 peak
on these shapes (``PERF.md`` section 6, PR 42), and was handed two things it
need not be:

* the WHOLE layer stack as ``L x E_held`` groups, all but one layer's
  empty, so that the bank is never sliced. Here the bank is that stack
  too (its free ``[L x E_held, K, N]`` view, never sliced, never copied),
  the group sizes are ONE layer's, and the layer is a scalar the bank's
  index map adds: the metadata covers 16-64 groups, not 324-1,216;
* rows of experts held on other chips (half to three quarters of a
  prefill dispatch's pairs), sorted past the last group. Here the grid is
  over the row tiles that hold a pair of a held expert, and its bound is
  read from the group sizes: a tile past the last group is never visited.

The schedule is ``jax.experimental.pallas.ops.tpu.megablox.gmm``'s (group
metadata as scalar prefetch, a masked store where a row tile straddles two
groups), with a bank tile that spans the whole contraction: a group's row
tiles are consecutive grid steps with one bank block index, so a bank tile
is fetched ONCE a call, and a row tile once for gate and up together.
Accumulation is float32 and every result is rounded once to the rows'
dtype at the store, where ``ragged_dot`` rounded.

Rows past the last group, and the rows of a straddled tile no group owns,
are never written, and never read: the caller gathers the rows of held
pairs alone (``moe.combine``; a pair held elsewhere reads row 0 and is
discarded by a ``where``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# What the kernel's blocks may take of a v5e core's 128 MiB of VMEM: two
# slots of each bank tile, of the row tile and of the result, and the
# float32 products.
VMEM_BLOCK_BYTES = 48 << 20
# Rows a grid step multiplies. A tile that straddles a group's end is
# multiplied once for each group in it, so a call pays ``E_held - 1`` tiles
# over its held rows; the MXU wants 128 rows or more a weight tile it
# loads. The sweep on the chip (``PERF.md`` section 6, PR 42: 64, 128 and
# 256 rows at 17-284 rows an expert, arithmetic-bound and stream-bound
# banks alike) read 128 best or within 1.6% of the best at all nine
# points: the straddles a wider tile adds cost what its weight loads save.
ROW_TILE = 128


def grouped_tiles(
    rows: int, hidden: int, width: int, itemsize: int = 2
) -> tuple[int, int, int]:
    """``(row tile, gate/up column tile, down column tile)`` of a call of
    ``rows`` sorted pairs over experts of ``[hidden, width]`` kernels.
    Pure: the static shapes alone, no setting and no family's name.

    The row tile is ``ROW_TILE``, or a call's few rows in whole sublane
    tiles of 16. The column tiles are the whole width, halved until the
    blocks fit ``VMEM_BLOCK_BYTES``; a bank tile spans the contraction, so
    the bank is read once.
    """
    row_tile = min(ROW_TILE, -(-rows // 16) * 16)

    def columns(contract: int, out: int, banks: int) -> int:
        tile = out
        while tile % 256 == 0 and _block_bytes(
            row_tile, contract, tile, banks, itemsize
        ) > VMEM_BLOCK_BYTES:
            tile //= 2
        return tile

    return row_tile, columns(hidden, width, 2), columns(width, hidden, 1)


def _block_bytes(row_tile, contract, columns, banks, itemsize) -> int:
    return (
        2 * banks * contract * columns * itemsize  # bank tiles, two slots
        + 2 * row_tile * contract * itemsize  # the row tile, two slots
        + 2 * row_tile * columns * itemsize  # the result, two slots
        + (banks + 1) * row_tile * columns * 4  # float32 products
    )


def _schedule(group_sizes, rows: int, row_tile: int):
    """The grid's middle axis: one step for each (group, row tile) that
    share a row. ``(ends [E], group_of [steps], tile_of [steps], active)``:
    the groups' last rows (exclusive), each step's group and row tile, and
    the traced count of steps the grid runs to; ``steps`` is the static
    most (every tile, and a straddle for each group but the first). A few
    dozen groups and a few hundred steps: the running sums and the lookups
    are compares against an ``arange``, which XLA fuses into eight ops a
    call where ``cumsum``, ``searchsorted`` and two gathers ran thirty
    more (0.15 ms a layer in PR 42's first traces)."""
    held = group_sizes.shape[0]
    index = jnp.arange(held, dtype=jnp.int32)
    upto = index[None, :] <= index[:, None]  # [E, E]: groups up to each one

    def running(counts):
        return jnp.sum(jnp.where(upto, counts[None, :], 0), axis=1)

    ends = running(group_sizes)
    first = (ends - group_sizes) // row_tile
    tiles = jnp.where(
        group_sizes > 0, (ends - 1) // row_tile - first + 1, 0
    )
    step_ends = running(tiles)
    step = jnp.arange(rows // row_tile + held - 1, dtype=jnp.int32)
    group_of = jnp.minimum(
        jnp.sum(step_ends[None, :] <= step[:, None], axis=1, dtype=jnp.int32),
        held - 1,
    )
    # the step's place in its group, from the group's first tile on
    of_group = group_of[:, None] == index[None, :]  # [steps, E]
    tile_of = step + jnp.sum(
        jnp.where(of_group, (first - step_ends + tiles)[None, :], 0), axis=1
    )
    return ends, group_of, tile_of, step_ends[-1]


def _grouped_matmul_kernel(
    first_group, ends, group_of, tile_of, rows, *refs, row_tile, gated,
    activation='silu',
):
    """One (column tile, step) of the grid: the step's row tile times its
    group's bank tile, stored into the rows the group owns. ``gated`` (two
    banks: gate, up) stores ``act(g) * u`` (``activation``: ``'silu'`` or
    ``'relu'``) with ``g`` and ``u`` rounded to the rows' dtype first, as
    two ``ragged_dot`` results were."""
    import jax.experimental.pallas as pl

    del first_group
    *banks, out = refs
    step = pl.program_id(1)
    group = group_of[step]
    row = tile_of[step] * row_tile + jax.lax.broadcasted_iota(
        jnp.int32, (row_tile, 1), 0
    )
    start = jnp.where(group > 0, ends[jnp.maximum(group - 1, 0)], 0)
    owned = (row >= start) & (row < ends[group])
    x = rows[...]
    products = [
        jnp.dot(x, bank[...], preferred_element_type=jnp.float32).astype(
            out.dtype
        )
        for bank in banks
    ]
    if gated:
        g, u = (p.astype(jnp.float32) for p in products)
        if activation == 'relu':
            result = (jnp.maximum(g, 0.0) * u).astype(out.dtype)
        else:
            result = (g * jax.nn.sigmoid(g) * u).astype(out.dtype)
    else:
        (result,) = products
    out[...] = jnp.where(owned, result, out[...])


def _grouped_matmul(
    rows, banks, schedule, first_group, row_tile, columns, interpret,
    activation='silu',
):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ends, group_of, tile_of, active = schedule
    contract, out_columns = banks[0].shape[-2:]
    if rows.shape[0] % row_tile or out_columns % columns:
        raise ValueError(
            f'{rows.shape[0]} rows x {out_columns} columns are not whole '
            f'({row_tile}, {columns}) tiles'
        )
    # (column tile, step): the steps of a group are consecutive and share
    # the bank's block index, so the bank tile is fetched once a group.
    bank_spec = pl.BlockSpec(
        (None, contract, columns),
        lambda n, s, first, ends, group_of, tile_of: (
            first[0] + group_of[s], 0, n
        ),
    )
    return pl.pallas_call(
        functools.partial(
            _grouped_matmul_kernel, row_tile=row_tile, gated=len(banks) == 2,
            activation=activation,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (rows.shape[0], out_columns), rows.dtype
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(out_columns // columns, active),
            in_specs=[
                pl.BlockSpec(
                    (row_tile, contract),
                    lambda n, s, first, ends, group_of, tile_of: (
                        tile_of[s], 0
                    ),
                ),
                *[bank_spec] * len(banks),
            ],
            out_specs=pl.BlockSpec(
                (row_tile, columns),
                lambda n, s, first, ends, group_of, tile_of: (
                    tile_of[s], n
                ),
            ),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_block_bytes(
                row_tile, contract, columns, len(banks), rows.dtype.itemsize
            ) + (16 << 20),
        ),
        interpret=interpret,
        name='grouped_matmul',
    )(first_group, ends, group_of, tile_of, rows, *banks)


@functools.partial(
    jax.jit, static_argnames=('tiles', 'interpret', 'activation')
)
def expert_matmuls(  # distlint: traced
    rows: jnp.ndarray,  # [M, K], sorted by group; M in whole row tiles
    gate: jnp.ndarray,  # [G, K, N]: E_held groups, or a stack's L x E_held
    up: jnp.ndarray,  # [G, K, N]
    down: jnp.ndarray,  # [G, N, K]
    group_sizes: jnp.ndarray,  # [E_held] int32: ONE layer's
    layer,  # the layer in the stack (an int32 scalar, traced or not); 0
    *,
    tiles: tuple[int, int, int],
    interpret: bool = False,
    activation: str = 'silu',
) -> jnp.ndarray:
    """``(act(rows @ gate_e) * (rows @ up_e)) @ down_e`` for the rows of
    each group ``e`` (``activation``: ``'silu'`` or ``'relu'``): ``[M, K]``
    in the rows' dtype, rows past the last group unwritten. One ``jax.jit``
    with the tiles static and the layer an operand: a program that calls it
    from 22 layers traces and lowers it once."""
    row_tile, up_columns, down_columns = tiles
    held = group_sizes.shape[0]
    schedule = _schedule(group_sizes, rows.shape[0], row_tile)
    first_group = (jnp.asarray(layer, jnp.int32) * held).reshape(1)
    hidden = _grouped_matmul(
        rows, (gate, up), schedule, first_group, row_tile, up_columns,
        interpret, activation,
    )
    return _grouped_matmul(
        hidden, (down,), schedule, first_group, row_tile, down_columns,
        interpret,
    )
