"""The gated delta rule with a decay a channel (Kimi delta attention,
arXiv:2510.26692), a float32 matrix state ``S [d_k, d_v]`` a head::

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

in two forms that compute the same recurrence: ``kda_step`` (decode: one
token a row, a plain XLA program) and ``kda_span`` (prefill: a span of every
row, from the state the span before it left, a chunk of positions at a
time). What computes the span form follows from the backend and the call's
static shapes (``span_form``): one Pallas kernel on a TPU where the head
sizes are whole lane tiles of 128 (``span_kernel``), a ``lax.scan`` over the
chunks as an XLA program everywhere else (``_span_scan``: the other
backends' path, odd head sizes, and the kernel's reference in the tests).
``benchmarks/solar_open2_bytes.py`` counts what either has to move and do,
whatever implements it. The way INTO the rule is here too, for a model whose
q, k and v pass a short causal convolution, SiLU and (q, k) an L2 norm a
head before the recurrence reads them (``inputs_kernel``, chosen by
``inputs_form`` as the span form is by ``span_form``; its section at the end
of the file says how).

The chunk form. With ``G_t`` the running sum of ``g`` inside a chunk that
starts from ``S_0``, and ``u_t = b_t (v_t - S_{t-1}^T (exp(g_t) k_t))`` the
rank-one correction's row::

    S_t = Diag(exp G_t) S_0 + sum_{j <= t} Diag(exp(G_t - G_j)) k_j u_j^T
    (I + A) U = b (V - (exp(G) K) S_0)      A[t, j] = b_t sum_c k_tc k_jc
                                            exp(G_tc - G_jc) for j < t
    O = (exp(G) Q) S_0 + P U                P[t, j] = sum_c q_tc k_jc
                                            exp(G_tc - G_jc) for j <= t

``I + A`` is unit lower triangular: one triangular solve a chunk (the WY /
UT form's inverse, never formed). The decays differ by channel, so ``A``
and ``P`` are not products of two scaled matrices: ``exp(G_t - G_j)`` is
formed from the DIFFERENCE, which is never positive where it is used, and
never as ``exp(G_t) * exp(-G_j)``, whose second factor overflows at the
strongest decays (a chunk of 32 steps at 0.2 a step is ``exp(51)``).
A position that does not count has ``b = 0`` and ``g = 0``: the state passes
through it.

The kernel. The grid is (row, group of heads, chunk), the chunk axis last
and sequential: a head's ``[128, 128]`` float32 state is read from HBM when
its first chunk starts, lives in VMEM across the span's chunks and is
written when the last ends (the scan wrote and read it every chunk). The
operands are read as they lie, ``[B, S, H d]`` blocks of a chunk and a group
of heads, in whatever dtype they come (the scan made ``[N, B, H, C, d]``
float32 copies of all five). Inside a chunk the positions are cut in
sub-blocks. ON the diagonal sub-blocks ``exp(G_t - G_j)`` is formed from the
difference as above, a column ``j`` at a time over the rows after it: the
exponentials and the two reductions over the channels are spent on the
sub-block's pairs alone, not on the chunk's. OFF the diagonal, for a row
block that starts at ``r + 1`` and every position ``j <= r`` before it::

    exp(G_t - G_j) = exp(G_t - G_r) exp(G_r - G_j)      j <= r < t

``G`` only falls, so BOTH exponents are at most zero whatever the decays:
neither factor passes one (what overflows is ``exp(-G_j)`` alone, a factor
taken relative to the chunk's start), and ``A`` and ``P`` there are matmuls of
``k`` and ``q`` scaled by rows. ``(I + A) U = rhs`` is solved by forward
substitution, by rows inside a sub-block as the columns of ``A`` come and by
blocks across them (a matmul with the rows solved so far); never by powers
of ``A``, whose entries reach 2 where ``b`` does. The products with the
carried state, ``P U`` and the state's update are matmuls too, every one at
``Precision.HIGHEST``. The state is held TRANSPOSED in VMEM (``[d_v, d_k]``):
both products with it then contract its lanes and the decay a channel scales
its columns, so no vector is ever turned from a row into a column.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

F32 = jnp.float32
try:  # the stack this module is imported from: ``_one_source``
    from jax._src.lib import xla_client as _xla_client

    _IMPORTED_AT = _xla_client.Traceback.get_traceback()
except (ImportError, AttributeError):
    _IMPORTED_AT = None
# Every product of the rule: float32 in, float32 out. At the TPU's default a
# float32 dot rounds its operands to bfloat16: from equal operands a row's
# state then lies 2e-4 to 2e-3 off the token-by-token recurrence's where
# these lie 1e-5 to 6e-5 (chip, PR 45: ``benchmarks/SOLAR_OPEN2.md``).
_EXACT = jax.lax.Precision.HIGHEST
# Positions a chunk of the span form. From one sweep on the chip at the
# published head sizes (4 rows x 512 tokens x 64 heads of 128; PR 45): a (512,
# 4) dispatch of one layer takes 8.5 ms at 32, 14.0 at 64, 103 at 16.
CHUNK = 32
# The kernel's chunk, the sub-block inside it, and the heads a grid step
# takes (the first that divides the call's). From the sweep on the chip at
# the same shapes (PR 46, ``PERF.md`` section 5: 2.11 ms a (512, 4) dispatch
# and layer here; 2.28 at a sub-block of 16, 2.72 at a chunk of 128 in
# sub-blocks of 64, 2.35 at two heads a step; a chunk of 128 in sub-blocks
# of 32 reads 1.98 and pads a ragged span to 128).
KERNEL_CHUNK = 64
KERNEL_SUB_BLOCK = 32
KERNEL_HEADS_A_STEP = (4, 2, 1)


def kda_step(q, k, v, g, beta, state):  # distlint: traced
    """One token of every row: ``q, k, g [B, H, d_k]``, ``v [B, H, d_v]``,
    ``beta [B, H]``, ``state [B, H, d_k, d_v]`` float32. Returns ``(o [B, H,
    d_v], state)``, float32. No matmul: the state is read and written once,
    and everything between is elementwise and two reductions over it."""
    q, k, v, g, beta = (t.astype(F32) for t in (q, k, v, g, beta))
    state = jnp.exp(g)[..., None] * state
    seen = jnp.sum(k[..., None] * state, axis=-2)  # S^T k: [B, H, d_v]
    state = state + (beta[..., None] * k)[..., None] * (v - seen)[..., None, :]
    return jnp.sum(q[..., None] * state, axis=-2), state


def span_backend() -> str:
    """What runs the span form: ``'pallas'`` (the kernel below) on a TPU,
    ``'xla'`` (the scan over chunks) elsewhere. ``'interpret'`` is the kernel
    on the Pallas interpreter: the tests set it, as they set ``'pallas'`` to
    compile for a described chip."""
    return 'pallas' if jax.default_backend() == 'tpu' else 'xla'


def span_form(
    backend: str, rows: int, span: int, heads: int, d_k: int, d_v: int
) -> tuple[int, int, int] | str:
    """The kernel's ``(chunk, sub-block, heads a grid step)`` for a call of
    ``rows`` rows of ``span`` positions, or ``'xla'`` where the scan runs
    it: off a TPU, and for a head size that is not whole lane tiles of 128.
    Pure: the backend and the call's static shapes, no setting and no
    family's name."""
    del rows, span  # one tiling served every shape of the sweep
    if backend == 'xla' or d_k % 128 or d_v % 128:
        return 'xla'
    step = next(n for n in KERNEL_HEADS_A_STEP if heads % n == 0)
    return KERNEL_CHUNK, KERNEL_SUB_BLOCK, step


def kda_span(q, k, v, g, beta, state, chunk: int = CHUNK):  # distlint: traced
    """A span of every row: ``q, k, g [B, S, H, d_k]``, ``v [B, S, H,
    d_v]``, ``beta [B, S, H]`` (``g`` and ``beta`` 0 where a position does
    not count), ``state [B, H, d_k, d_v]`` float32. Returns ``(o [B, S, H,
    d_v], state)``, float32: the recurrence's, at any ``chunk`` and any
    split of a sequence into spans. ``span_form`` says what computes it:
    the kernel at its own chunk, or the scan at ``chunk``."""
    backend = span_backend()
    form = span_form(backend, *q.shape[:3], q.shape[-1], v.shape[-1])
    if form != 'xla':
        chunk = form[0]
    s = q.shape[1]
    if s % chunk:  # whole chunks: the rest are positions that do not count
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, -s % chunk)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    if form == 'xla':
        o, state = _span_scan(q, k, v, g, beta, state, chunk)
    else:
        o, state = span_kernel(
            q, k, v, g, beta, state, form=form,
            interpret=backend == 'interpret',
        )
    return o[:, :s], state


def _span_scan(q, k, v, g, beta, state, chunk):
    """The span form as an XLA program: a ``lax.scan`` over the span's
    whole chunks."""
    bsz, s, h, d_k = q.shape
    n_chunks = s // chunk

    def split(t):  # [B, S, H, ...] -> [N, B, H, C, ...]
        t = t.reshape(bsz, n_chunks, chunk, *t.shape[2:]).astype(F32)
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)

    at_or_before = jnp.tril(jnp.ones((chunk, chunk), bool))
    before = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def one_chunk(s0, xs):
        q_c, k_c, v_c, g_c, b_c = xs  # [B, H, C, d], beta [B, H, C]
        cum = jnp.cumsum(g_c, axis=2)  # [B, H, C, d_k], decreasing
        # exp(G_t - G_j) k_j for j <= t, from the difference (never > 0).
        diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B, H, Ct, Cj, d_k]
        decayed_k = k_c[:, :, None, :, :] * jnp.exp(
            jnp.where(at_or_before[:, :, None], diff, -jnp.inf)
        )
        a = b_c[..., None] * jnp.where(
            before,
            jnp.einsum('bhtc,bhtjc->bhtj', k_c, decayed_k, precision=_EXACT),
            0.0,
        )
        p = jnp.einsum('bhtc,bhtjc->bhtj', q_c, decayed_k, precision=_EXACT)
        from_start = jnp.exp(cum)  # [B, H, C, d_k]
        rhs = b_c[..., None] * (
            v_c - jnp.einsum(
                'bhtc,bhcv->bhtv', from_start * k_c, s0, precision=_EXACT
            )
        )
        # unit_diagonal: the solve takes the diagonal as ones and never reads
        # it, so ``a`` (zero on and above it) stands for ``I + A``
        u = solve_triangular(a, rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum(
            'bhtc,bhcv->bhtv', from_start * q_c, s0, precision=_EXACT
        ) + jnp.einsum('bhtj,bhjv->bhtv', p, u, precision=_EXACT)
        to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [B, H, C, d_k]
        s1 = from_start[:, :, -1, :, None] * s0 + jnp.einsum(
            'bhtc,bhtv->bhcv', to_end * k_c, u, precision=_EXACT
        )
        return s1, o

    state, o = jax.lax.scan(
        one_chunk, state.astype(F32), tuple(split(t) for t in (q, k, v, g, beta))
    )
    # [N, B, H, C, d_v] -> [B, S, H, d_v]
    return jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(bsz, s, h, -1), state


# ------------------------------------------------------ the span form's kernel
def _dot(a, b, contract=((1,), (0,))):
    """A float32-grade product of two float32 matrices (the MXU's six
    bfloat16 passes), contracting ``a``'s and ``b``'s given axes."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), precision=_EXACT,
        preferred_element_type=F32,
    )


def _below(rows, top: int, new):
    """``rows`` with those from ``top`` on replaced by ``new``."""
    return jnp.concatenate([rows[:top], new], axis=0) if top else new


def _chunk_of_a_head(q, k, v, g, beta, carried, sub):
    """One chunk of one head inside the kernel: ``q, k, g [C, d_k]``, ``v
    [C, d_v]``, ``beta [C, 1]`` float32 and the carried state TRANSPOSED,
    ``[d_v, d_k]`` (both products with it contract its lanes, and the decay
    a channel scales its columns: no vector is ever turned). Returns ``(o
    [C, d_v], state [d_v, d_k])``."""
    from jax.experimental.pallas import tpu as pltpu

    chunk = q.shape[0]
    iota = jax.lax.broadcasted_iota
    # G, the running sum of g down the chunk, by doubling shifts
    cum, position, shift = g, iota(jnp.int32, g.shape, 0), 1
    while shift < chunk:
        cum = cum + jnp.where(position >= shift, pltpu.roll(cum, shift, 0), 0.0)
        shift *= 2
    from_start = jnp.exp(cum)
    through = _dot(
        jnp.concatenate([from_start * k, from_start * q], axis=0),
        carried, ((1,), (1,)),
    )  # (exp(G) K) S_0 over (exp(G) Q) S_0
    rhs = beta * (v - through[:chunk])
    u_blocks, p_rows = [], []
    for lo in range(0, chunk, sub):
        rows = slice(lo, lo + sub)
        cum_b, k_b, q_b = cum[rows], k[rows], q[rows]
        beta_k = beta[rows] * k_b
        x = rhs[rows]
        if lo:
            # Off the diagonal: through the last position before the block,
            # exp(G_t - G_r) exp(G_r - G_j), neither exponent positive. The
            # columns from ``lo`` on are products with zero rows.
            edge = cum[lo - 1:lo]
            into = jnp.exp(cum_b - edge)
            out_of = jnp.concatenate(
                [jnp.exp(edge - cum[:lo]) * k[:lo],
                 jnp.zeros((chunk - lo, k.shape[1]), F32)], axis=0,
            )
            off = _dot(
                jnp.concatenate([into * beta_k, into * q_b], axis=0),
                out_of, ((1,), (1,)),
            )  # A's rows over P's, [2 sub, C]
            p_row = off[sub:]
            x = x - _dot(off[:sub], jnp.concatenate(
                u_blocks + [jnp.zeros((chunk - lo, x.shape[1]), F32)], axis=0
            ))
        else:
            p_row = jnp.zeros((sub, chunk), F32)
        # On the diagonal: exp(G_t - G_j) from the difference, a column a
        # position j over the rows after it (whole sublane tiles of them),
        # and forward substitution by rows as the columns of A come.
        for j in range(sub - 1):
            top = j // 8 * 8
            after = iota(jnp.int32, (sub - top, 1), 0) > j - top
            decayed_k = k_b[j:j + 1] * jnp.exp(
                jnp.where(after, cum_b[top:] - cum_b[j:j + 1], -jnp.inf)
            )
            a_col = jnp.sum(beta_k[top:] * decayed_k, axis=-1, keepdims=True)
            p_col = jnp.sum(q_b[top:] * decayed_k, axis=-1, keepdims=True)
            here = iota(jnp.int32, (sub - top, chunk), 1) == lo + j
            p_row = _below(p_row, top, jnp.where(here, p_col, p_row[top:]))
            x = _below(x, top, x[top:] - a_col * x[j:j + 1])
        own = iota(jnp.int32, (sub, chunk), 1) == lo + iota(
            jnp.int32, (sub, chunk), 0
        )
        p_rows.append(jnp.where(
            own, jnp.sum(q_b * k_b, axis=-1, keepdims=True), p_row
        ))
        u_blocks.append(x)
    u = jnp.concatenate(u_blocks, axis=0)
    o = through[chunk:] + _dot(jnp.concatenate(p_rows, axis=0), u)
    to_end = jnp.exp(cum[chunk - 1:] - cum)
    carried = carried * from_start[chunk - 1:] + _dot(
        u, to_end * k, ((0,), (0,))
    )
    return o, carried


def _span_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, state0_ref, o_ref, state_ref,
    carried, *, sub, heads,
):
    """One (row, group of heads, chunk) of the grid. The chunk axis is the
    last and sequential: a head's state is read when its first chunk
    starts, lives transposed in ``carried`` across the span's chunks and is
    written when the last ends."""
    import jax.experimental.pallas as pl

    chunk_id = pl.program_id(2)

    @pl.when(chunk_id == 0)
    def _():
        for i in range(heads):
            carried[i] = state0_ref[0, i].T

    d_k = q_ref.shape[2] // heads
    d_v = v_ref.shape[2] // heads
    betas = beta_ref[0]  # [C, H]: every head's
    head_of = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    for i in range(heads):
        keys, values = slice(i * d_k, (i + 1) * d_k), slice(i * d_v, (i + 1) * d_v)
        beta = jnp.sum(
            jnp.where(head_of == pl.program_id(1) * heads + i, betas, 0.0),
            axis=-1, keepdims=True,
        )
        o, carried[i] = _chunk_of_a_head(
            q_ref[0, :, keys].astype(F32), k_ref[0, :, keys].astype(F32),
            v_ref[0, :, values].astype(F32), g_ref[0, :, keys].astype(F32),
            beta, carried[i], sub,
        )
        o_ref[0, :, values] = o

    @pl.when(chunk_id == pl.num_programs(2) - 1)
    def _():
        for i in range(heads):
            state_ref[0, i] = carried[i].T


@contextlib.contextmanager
def _one_source():
    """What the kernel is traced under, so that its serialized body is the
    same bytes whoever traces it first. A Mosaic body carries the debug
    locations of its operations, and jax caches a traced ``jax.jit`` (this
    module's, and every ``jnp`` function the body calls) with the call stack
    of its FIRST caller; the body is an opaque string of the program's
    custom call, so what the persistent compile cache strips from a
    program's own locations it keeps here. The cell's check lowers this
    kernel on a thread beside the engine's warm-up, and which of the two
    came first flipped between a cold and a warm run: every prefill program
    then missed the cache once more (chip, PR 46: 28 s of set-up). So the
    trace gets a context of its own (a matmul precision nothing else in a
    process asks for, which is also what every product here states) and
    ONE traceback, this module's import. Both are jax-internal; without
    them the kernel is as right and a cache hit less sure."""
    try:
        from jax._src import source_info_util
        pinned = source_info_util.user_context(_IMPORTED_AT)
    except (ImportError, AttributeError):  # another jax: no pin
        pinned = contextlib.nullcontext()
    with jax.default_matmul_precision('highest'), pinned:
        yield


@functools.partial(jax.jit, static_argnames=('form', 'interpret'))
def span_kernel(  # distlint: traced
    q, k, v, g, beta, state, *, form: tuple[int, int, int],
    interpret: bool = False,
):
    """``kda_span`` as one Pallas TPU kernel at ``form`` (chunk, sub-block,
    heads a grid step: ``span_form``'s) over a span of whole chunks. The
    operands are read as they lie (``[B, S, H d]`` is a free view, in
    whatever dtype they come; the casts to float32 are inside)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk, sub, heads = form
    bsz, s, h, d_k = q.shape
    d_v = v.shape[-1]
    if (
        s % chunk or chunk % sub or sub % 8 or h % heads or d_k % 128
        or d_v % 128
    ):
        raise ValueError(f'{form} does not tile {q.shape} x {v.shape}')
    q, k, v, g = (t.reshape(bsz, s, -1) for t in (q, k, v, g))

    def tokens(width):  # a chunk of a group of heads, as it lies
        return pl.BlockSpec(
            (1, chunk, heads * width), lambda b, hg, c: (b, c, hg)
        )

    # the group's states: the same block at every chunk
    matrices = pl.BlockSpec(
        (1, heads, d_k, d_v), lambda b, hg, c: (b, hg, 0, 0)
    )

    with _one_source():
        o, state = pl.pallas_call(
            functools.partial(_span_kernel, sub=sub, heads=heads),
            out_shape=(
                jax.ShapeDtypeStruct((bsz, s, h * d_v), F32),
                jax.ShapeDtypeStruct(state.shape, F32),
            ),
            grid=(bsz, h // heads, s // chunk),
            in_specs=[
                tokens(d_k), tokens(d_k), tokens(d_v), tokens(d_k),
                pl.BlockSpec((1, chunk, h), lambda b, hg, c: (b, c, 0)),
                matrices,
            ],
            out_specs=(tokens(d_v), matrices),
            scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            ),
            interpret=interpret,
            name='kda_span',
        )(q, k, v, g, beta.astype(F32), state.astype(F32))
    return o.reshape(bsz, s, h, d_v), state


# ------------------------------------------------ the way in: q, k, v in one pass
# What the recurrence reads of a span are not the projections' outputs but,
# a channel, ``silu(sum_j w[j] x[t - (K - 1) + j])`` over the last K inputs
# (the span's own behind the K - 1 rows the span before left), and of q and k
# that vector over its norm a head. As XLA programs that is a float32 copy of
# the whole input, the taps' shifted sum over it, and a norm's three passes:
# 3.2 ms a (512, 4) dispatch and layer at the published widths where its own
# bytes (bfloat16 read, float32 written) are 0.37 ms at the HBM rate (chip,
# PR 47). The kernel reads each projection's output once and writes q, k, v
# once; everything between stays in VMEM. The grid is (row, channel tile,
# sequence tile), a channel tile whole heads (a norm never crosses one) and
# the same tile of q's, k's and v's third a step; the sequence axis is the
# last and sequential, and the rows before a tile are carried in scratch.
# The sequence tiles the way in may take (the first that divides the span;
# the bfloat16 sublane tile is the least), the rows a step of the loop inside
# a tile, and the heads a grid step (the first that divides the call's).
INPUTS_SEQ_TILES = (512, 256, 128, 64, 32, 16)
INPUTS_ROWS_A_STEP = 128
INPUTS_HEADS_A_STEP = (2, 1)
_HALO = 8  # a float32 sublane tile: the rows before a tile are kept in one


def inputs_form(
    backend: str, rows: int, span: int, channels: int, taps: int, head: int
) -> tuple[int, int, int] | str:
    """The way-in kernel's ``(sequence tile, rows a step of the loop inside
    it, channel tile)`` for a call of ``rows`` rows of ``span`` positions
    over ``channels`` = 3 H d convolution channels of ``taps`` taps and
    heads of ``head``, or ``'xla'`` where the model's XLA form runs it: off
    a TPU, for a head that is not whole lane tiles of 128, for a span that
    is not whole sublane tiles (a decode step's one position, a ragged
    tail), and for more carried rows than one sublane tile holds. Pure, as
    ``span_form``."""
    del rows  # a grid axis of its own
    if (
        backend == 'xla' or head % 128 or channels % (3 * head)
        or span % INPUTS_SEQ_TILES[-1] or not 2 <= taps <= _HALO + 1
    ):
        return 'xla'
    heads = channels // (3 * head)
    tile = next(n for n in INPUTS_SEQ_TILES if span % n == 0)
    return (
        tile, min(INPUTS_ROWS_A_STEP, tile),
        head * next(n for n in INPUTS_HEADS_A_STEP if heads % n == 0),
    )


def _third_of_a_tile(
    x_ref, c_ref, w_ref, o_ref, halo_ref, *, step, head, scale, eps
):
    """One third's (row, channel tile, sequence tile): ``x_ref [1, T, C]``
    the projection's output, ``c_ref [1, K - 1, C]`` the rows carried into
    the span, ``w_ref [K, C]`` the taps, ``halo_ref [_HALO, C]`` float32 the
    rows before the tile: from the carried rows at a span's first tile, from
    the tile before after it. Inside the tile a loop of ``step`` rows: the
    taps' sum over the rows and the halo shifted down by sublane rolls,
    SiLU, and where ``scale`` is given (q and k) each head's vector over its
    norm, times ``scale``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, width = x_ref.shape[1:]
    taps = w_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        # the K - 1 carried rows, at the end of the halo's tile
        rows, halo = c_ref[0].astype(F32), jnp.zeros((_HALO, width), F32)
        at = jax.lax.broadcasted_iota(jnp.int32, halo.shape, 0)
        for i in range(taps - 1):
            halo = jnp.where(at == _HALO - (taps - 1) + i, rows[i:i + 1], halo)
        halo_ref[...] = halo

    w = w_ref[...].astype(F32)

    def rows_of_a_step(i, before):
        lo = pl.multiple_of(i * step, step)
        x = x_ref[0, pl.ds(lo, step), :].astype(F32)
        window = jnp.concatenate([before, x], axis=0)
        # sum_j w[j] window[t + j], oldest tap first (the XLA form's order)
        y = w[:1] * pltpu.roll(window, taps - 1, 0)[_HALO:]
        for j in range(1, taps - 1):
            y = y + w[j:j + 1] * pltpu.roll(window, taps - 1 - j, 0)[_HALO:]
        y = y + w[taps - 1:] * x
        y = y * jax.nn.sigmoid(y)
        if scale is None:  # v: no norm
            o_ref[0, pl.ds(lo, step), :] = y
        else:  # q and k: a head over its norm
            for h in range(width // head):
                lanes = slice(h * head, (h + 1) * head)
                y_h = y[:, lanes]
                normed = y_h * jax.lax.rsqrt(
                    jnp.sum(y_h * y_h, axis=-1, keepdims=True) + eps
                )
                o_ref[0, pl.ds(lo, step), lanes] = (
                    normed if scale == 1.0 else normed * scale
                )
        return x[step - _HALO:]

    halo_ref[...] = jax.lax.fori_loop(
        0, tile // step, rows_of_a_step, halo_ref[...]
    )


def _inputs_kernel(
    x_q, x_k, x_v, c_q, c_k, c_v, w_q, w_k, w_v, q_ref, k_ref, v_ref, carried,
    *, q_scale, **static,
):
    """One (row, channel tile, sequence tile) of the grid: the same tile of
    q's, k's and v's third of the channels. The sequence axis is the last
    and sequential: ``carried [3, _HALO, C]`` holds each third's rows before
    the tile."""
    _third_of_a_tile(x_q, c_q, w_q, q_ref, carried.at[0], scale=q_scale, **static)
    _third_of_a_tile(x_k, c_k, w_k, k_ref, carried.at[1], scale=1.0, **static)
    _third_of_a_tile(x_v, c_v, w_v, v_ref, carried.at[2], scale=None, **static)


@functools.partial(
    jax.jit,
    static_argnames=('form', 'head', 'q_scale', 'eps', 'interpret'),
)
def inputs_kernel(  # distlint: traced
    projected, conv0, taps, *, form: tuple[int, int, int], head: int,
    q_scale: float, eps: float, interpret: bool = False,
):
    """The way into the rule as one Pallas TPU kernel at ``form`` (sequence
    tile, rows a step, channel tile: ``inputs_form``'s): of the q, k and v
    projections' outputs ``projected = (q~, k~, v~)``, ``[B, S, H d]`` each,
    behind the
    carried rows ``conv0 [B, K - 1, 3 H d]`` (q's, k's, v's side by side),
    all read as they lie in whatever dtype they come, the causal depthwise
    convolution of ``taps [K, 3 H d]``, SiLU and, for q and k, each head's
    L2 norm (``eps`` under the root) and q's ``q_scale``. Returns ``q, k, v
    [B, S, H d]`` float32. Every sum, the logistic and the root are float32
    on the vector unit; no intermediate leaves VMEM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, step, width = form
    bsz, s, third = projected[0].shape
    n_taps = taps.shape[0]
    if (
        s % tile or tile % step or step % _HALO
        or third % width or width % head or head % 128
        or not 2 <= n_taps <= _HALO + 1
        or any(t.shape != (bsz, s, third) for t in projected)
        or conv0.shape != (bsz, n_taps - 1, 3 * third)
        or taps.shape != (n_taps, 3 * third)
    ):
        raise ValueError(
            f'{form} does not tile three of {projected[0].shape} behind '
            f'{conv0.shape} with taps {taps.shape} and heads of {head}'
        )
    tiles = third // width  # channel tiles a third: q's, then k's, then v's
    # a third's own tile of the span: the projections' and the results'
    tokens = pl.BlockSpec((1, tile, width), lambda b, c, t: (b, t, c))

    def thirds(block, index):  # the same tile of each third, side by side
        return [
            pl.BlockSpec(block, functools.partial(index, first=i * tiles))
            for i in range(3)
        ]

    with _one_source():
        return pl.pallas_call(
            functools.partial(
                _inputs_kernel, step=step, head=head, q_scale=q_scale, eps=eps
            ),
            out_shape=(jax.ShapeDtypeStruct((bsz, s, third), F32),) * 3,
            grid=(bsz, tiles, s // tile),
            in_specs=[
                tokens, tokens, tokens,
                *thirds(
                    (1, n_taps - 1, width),
                    lambda b, c, t, first: (b, 0, first + c),
                ),
                *thirds((n_taps, width), lambda b, c, t, first: (0, first + c)),
            ],
            out_specs=[tokens, tokens, tokens],
            scratch_shapes=[pltpu.VMEM((3, _HALO, width), F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            ),
            interpret=interpret,
            name='kda_inputs',
        )(*projected, conv0, conv0, conv0, taps, taps, taps)
