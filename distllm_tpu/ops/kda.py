"""The gated delta rule with a decay a channel (Kimi delta attention,
arXiv:2510.26692), a float32 matrix state ``S [d_k, d_v]`` a head::

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

in two forms that compute the same recurrence: ``kda_step`` (decode: one
token a row) and ``kda_span`` (prefill: a span of every row, from the state
the span before it left, ``chunk`` positions at a time). Both are plain XLA
programs; ``benchmarks/solar_open2_bytes.py`` counts what either has to move
and do, whatever implements it.

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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

F32 = jnp.float32
# Every product of the rule: float32 in, float32 out. At the TPU's default a
# float32 dot rounds its operands to bfloat16: from equal operands a row's
# state then lies 2e-4 to 2e-3 off the token-by-token recurrence's where
# these lie 1e-5 to 6e-5 (chip, PR 45: ``benchmarks/SOLAR_OPEN2.md``).
_EXACT = jax.lax.Precision.HIGHEST
# Positions a chunk of the span form. From one sweep on the chip at the
# published head sizes (4 rows x 512 tokens x 64 heads of 128; PR 45): a (512,
# 4) dispatch of one layer takes 8.5 ms at 32, 14.0 at 64, 103 at 16.
CHUNK = 32


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


def kda_span(q, k, v, g, beta, state, chunk: int = CHUNK):  # distlint: traced
    """A span of every row: ``q, k, g [B, S, H, d_k]``, ``v [B, S, H,
    d_v]``, ``beta [B, S, H]`` (``g`` and ``beta`` 0 where a position does
    not count), ``state [B, H, d_k, d_v]`` float32. Returns ``(o [B, S, H,
    d_v], state)``, float32: the recurrence's, at any ``chunk`` and any
    split of a sequence into spans."""
    bsz, s, h, d_k = q.shape
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    n_chunks = (s + pad) // chunk

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
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(bsz, s + pad, h, -1)
    return o[:, :s], state
