"""Validate the Pallas paged-attention kernel compiled on the real TPU:
correctness vs the XLA path, then a timing comparison at bench shapes."""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.ops.paged_attention import (
    paged_attention_pallas,
    paged_attention_xla,
)


def run(b, heads, kv, hd, bs, nblocks, mb, window=None, dtype=jnp.bfloat16):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, heads, hd)), dtype)
    k_cache = jnp.asarray(rng.normal(size=(nblocks, bs, kv, hd)), dtype)
    v_cache = jnp.asarray(rng.normal(size=(nblocks, bs, kv, hd)), dtype)
    # Distinct random block tables per sequence (blocks 1..nblocks-1).
    bt = np.zeros((b, mb), np.int32)
    ctx = rng.integers(1, mb * bs, size=(b,)).astype(np.int32)
    for i in range(b):
        need = -(-int(ctx[i]) // bs)
        bt[i, :need] = rng.choice(np.arange(1, nblocks), size=need, replace=False)
    bt = jnp.asarray(bt)
    ctx = jnp.asarray(ctx)

    f_xla = jax.jit(
        lambda *a: paged_attention_xla(*a, sliding_window=window)
    )
    f_pl = jax.jit(
        lambda *a: paged_attention_pallas(*a, sliding_window=window)
    )
    out_x = np.asarray(f_xla(q, k_cache, v_cache, bt, ctx), np.float32)
    out_p = np.asarray(f_pl(q, k_cache, v_cache, bt, ctx), np.float32)
    err = np.max(np.abs(out_x - out_p))
    print(f'b={b} heads={heads} kv={kv} hd={hd} bs={bs} mb={mb} '
          f'window={window}: max abs err = {err:.4f}')
    assert err < 0.1, 'MISMATCH'

    def bench(f, n=20):
        s = np.asarray(f(q, k_cache, v_cache, bt, ctx)).sum()  # warm+sync
        start = time.perf_counter()
        for _ in range(n):
            out = f(q, k_cache, v_cache, bt, ctx)
        np.asarray(out)
        return (time.perf_counter() - start) / n, s

    tx, _ = bench(f_xla)
    tp, _ = bench(f_pl)
    print(f'  xla {1e3*tx:.2f} ms   pallas {1e3*tp:.2f} ms   '
          f'(one layer-equivalent call)')


if __name__ == '__main__':
    # Small correctness shapes (head_dim must be 128-aligned compiled).
    run(4, 8, 4, 128, 16, 32, 8)
    run(4, 8, 4, 128, 16, 32, 8, window=40)
    # 7B decode shapes (one layer): batch 24, 32 heads, 8 kv, 128 hd.
    run(24, 32, 8, 128, 16, 488, 32)
    run(24, 32, 8, 128, 16, 488, 32, window=256)
    run(64, 32, 8, 128, 32, 512, 16)
