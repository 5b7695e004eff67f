"""int8 decode-window A/B: which quantized-matmul tier should serve?

Same isolated-window methodology as probe_decode.py (one jitted 16-step
unrolled window, 4 windows chained, one host sync) but with int8-quantized weights, at the
serving batch (128, 2840 blocks — bench gen_q dims) and the bf16 batch
(32) for cross-reference.

Context (a 2026-07-31 record on older code, in git history; not
re-measured, a hypothesis): run 1 served int8 via dequant-before-dot at
1242 ms/window; run 2 picked up the Pallas in-VMEM-dequant kernel and got
SLOWER (2046 ms). The isolated-matmul probe can't see why (dispatch-bound
at 1.3 ms/call), so this times the real window per tier. Floor at batch
128: 16 steps x 7.25 GB int8 / 819 GB/s = 142 ms + ~60 ms KV reads.
"""

from __future__ import annotations

import os
import pathlib as _pl
import sys as _sys

_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import mistral
from distllm_tpu.ops import quantized_matmul as qmm
from distllm_tpu.ops.quantization import quantize_pytree


def main() -> None:
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
        batches = ((8, 128),)
        cases = (('xla', 'xla'),)
    else:
        cfg = mistral.MistralConfig(dtype='bfloat16')
        batches = ((32, 712), (128, 2840))
        # First sweep (05:52 log) settled the qmm tier: xla scale-after-dot
        # beats the pallas dequant kernel at every serving shape. Remaining
        # question is the ATTENTION backend at int8 batches: the xla paged
        # path materializes a [B, 512, kv, 128] gather per layer-step,
        # which scales with batch and is the prime suspect for batch 128
        # sitting 7x off the weight floor.
        cases = (('xla', 'xla'), ('xla', 'pallas'))

    block_size = 16
    max_blocks = 512 // block_size
    params = mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    params = quantize_pytree(
        params, mode='int8', out_dtype=cfg.dtype, delete_source=True
    )
    int8_gb = n_params / 1e9
    print(f'int8 weights ~{int8_gb:.1f} GB', flush=True)

    num_steps = 16
    ctx = 160
    rng = np.random.default_rng(0)
    for batch, num_blocks in batches:
        kshape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads,
                  cfg.head_size)
        ids = jnp.asarray(
            rng.integers(1, cfg.vocab_size, size=(batch,)), jnp.int32
        )
        positions = jnp.full((batch,), ctx - 1, jnp.int32)
        context_lens = jnp.full((batch,), ctx, jnp.int32)
        rows = np.zeros((batch, max_blocks), np.int32)
        used = -(-ctx // block_size) + 3
        for b in range(batch):
            rows[b, :used] = 1 + (np.arange(used) * batch + b) % (
                num_blocks - 1
            )
        block_tables = jnp.asarray(rows)
        temp = jnp.full((batch,), 0.5, jnp.float32)
        top_p = jnp.full((batch,), 0.95, jnp.float32)
        min_p = jnp.full((batch,), 0.1, jnp.float32)
        steps_left = jnp.full((batch,), num_steps, jnp.int32)
        top_k = jnp.zeros((batch,), jnp.int32)
        seeds = jnp.ones((batch,), jnp.uint32)

        for qmm_backend, attn_backend in cases:
            qmm.set_default_backend(qmm_backend)
            fn = jax.jit(
                lambda p, i, po, c, k, v, bt, sl, t, tp, mp, tk, sd, ab=attn_backend: (
                    mistral.decode_loop(
                        p, cfg, i, po, k, v, bt, c, sl, t, tp, mp, tk, sd,
                        num_steps=num_steps, attn_backend=ab,
                        max_table_positions=512, sampling_top_window=64,
                        layer_unroll=True,
                    )
                ),
                donate_argnums=(4, 5),
            )
            k_cache = jnp.zeros(kshape, jnp.bfloat16)
            v_cache = jnp.zeros(kshape, jnp.bfloat16)
            try:
                t0 = time.perf_counter()
                tokens, k_cache, v_cache, _ = fn(
                    params, ids, positions, context_lens, k_cache, v_cache,
                    block_tables, steps_left, temp, top_p, min_p, top_k,
                    seeds,
                )
                np.asarray(tokens)
                compile_s = time.perf_counter() - t0
                n_reps = 4
                t0 = time.perf_counter()
                outs = []
                for _ in range(n_reps):
                    tokens, k_cache, v_cache, _ = fn(
                        params, ids, positions, context_lens, k_cache,
                        v_cache, block_tables, steps_left, temp, top_p,
                        min_p, top_k, seeds,
                    )
                    outs.append(tokens)
                for t in outs:
                    np.asarray(t)
                best = (time.perf_counter() - t0) / n_reps
                floor = num_steps * n_params / 819e9
                print(
                    f'batch={batch:3d} qmm={qmm_backend:6s}'
                    f' attn={attn_backend:6s}:'
                    f' {best * 1e3:7.1f} ms/window'
                    f' ({batch * num_steps / best:7.0f} tok/s,'
                    f' int8 floor {floor * 1e3:4.0f} ms, x{best / floor:4.1f},'
                    f' compile {compile_s:.0f} s)',
                    flush=True,
                )
            except Exception as exc:
                print(
                    f'batch={batch:3d} qmm={qmm_backend:6s}'
                    f' attn={attn_backend:6s}:'
                    f' FAILED {repr(exc)[:200]}',
                    flush=True,
                )
            finally:
                qmm.set_default_backend('auto')
        del k_cache, v_cache


if __name__ == '__main__':
    main()
