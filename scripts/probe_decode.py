"""Isolated decode-window microbench at 7B dims on the real chip.

probe_gen times the full serving loop; this times ONE fused decode window
dispatch in isolation across the knobs that matter, to localize the gap
between the window time on the chip and the ~283 ms weight-streaming floor
(14.5 GB x 16 steps / 819 GB/s):

- attention backend: pallas vs xla
- window length: decode_steps 1 / 8 / 16 / 32 (per-token cost should fall
  as dispatch overhead amortizes; if it doesn't, the per-step compute is
  the problem, not dispatch)
- sampler: top-64 window vs exact full-vocab sort (the 32k bitonic sort
  per step is a prime suspect)
- layer scan rolled vs unrolled at the serving window (the materialized
  weight-slice hypothesis, scripts/probe_decode_hlo.py)
"""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import mistral


def main() -> None:
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
        batch, num_blocks, steps_list = 8, 128, (1, 8)
        backends = ('xla',)
    else:
        cfg = mistral.MistralConfig(dtype='bfloat16')
        batch, num_blocks, steps_list = 32, 712, (1, 8, 16, 32)
        backends = ('pallas', 'xla')

    block_size = 16
    max_blocks = 512 // block_size
    params = mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    kshape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads,
              cfg.head_size)

    rng = np.random.default_rng(0)
    ctx = 160  # mid-run context length
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(batch,)), jnp.int32)
    positions = jnp.full((batch,), ctx - 1, jnp.int32)
    context_lens = jnp.full((batch,), ctx, jnp.int32)
    rows = np.zeros((batch, max_blocks), np.int32)
    used = -(-ctx // block_size) + 3
    for b in range(batch):
        rows[b, :used] = 1 + (np.arange(used) * batch + b) % (num_blocks - 1)
    block_tables = jnp.asarray(rows)
    temp = jnp.full((batch,), 0.5, jnp.float32)
    top_p = jnp.full((batch,), 0.95, jnp.float32)
    min_p = jnp.full((batch,), 0.1, jnp.float32)
    top_k = jnp.zeros((batch,), jnp.int32)
    seeds = jnp.ones((batch,), jnp.uint32)

    weight_gb = 2 * n_params / 1e9
    print(f'batch={batch} ctx={ctx} weights={weight_gb:.1f} GB')
    cases = [(be, ns, 64, False) for be in backends for ns in steps_list]
    # Sampler ablation: exact 32k sort at the serving window length.
    cases.append((backends[0], steps_list[-1], 0, False))
    # The rolled-vs-unrolled A/B at the SERVING window length (16 — the
    # shape behind the r3 845 ms measurement and the 283 ms floor; the
    # materialized weight-slice hypothesis, scripts/probe_decode_hlo.py):
    # unrolled should approach the floor if the slices were the gap.
    serving_steps = 16 if 16 in steps_list else steps_list[-1]
    for be in backends:
        cases.append((be, serving_steps, 64, True))
    for backend, num_steps, top_window, unroll in cases:
            fn = jax.jit(
                lambda p, i, po, c, k, v, bt, sl, t, tp, mp, tk, sd,
                       ns=num_steps,
                       be=backend, tw=top_window, un=unroll: mistral.decode_loop(
                    p, cfg, i, po, k, v, bt, c, sl, t, tp, mp, tk, sd,
                    num_steps=ns, attn_backend=be, max_table_positions=512,
                    sampling_top_window=tw, layer_unroll=un,
                ),
                donate_argnums=(4, 5),
            )
            steps_left = jnp.full((batch,), num_steps, jnp.int32)
            # Fresh caches per case: donation deletes them on dispatch, so
            # a mid-case failure (the flaky-chip scenario this probe
            # exists for) must not cascade 'Array deleted' into the rest.
            k_cache = jnp.zeros(kshape, jnp.bfloat16)
            v_cache = jnp.zeros(kshape, jnp.bfloat16)
            try:
                t0 = time.perf_counter()
                out = fn(params, ids, positions, context_lens, k_cache,
                         v_cache, block_tables, steps_left, temp, top_p,
                         min_p, top_k, seeds)
                tokens, k_cache, v_cache, _ = out
                np.asarray(tokens)
                compile_s = time.perf_counter() - t0
                # Chain 4 windows without per-call host syncs (donated
                # caches chain naturally); one final fetch, so the host
                # sync amortizes instead of padding each call.
                n_reps = 4
                t0 = time.perf_counter()
                outs = []
                for _ in range(n_reps):
                    tokens, k_cache, v_cache, _ = fn(
                        params, ids, positions, context_lens, k_cache,
                        v_cache, block_tables, steps_left, temp, top_p,
                        min_p, top_k, seeds)
                    outs.append(tokens)
                for t in outs:
                    np.asarray(t)
                best = (time.perf_counter() - t0) / n_reps
                floor = num_steps * 2 * n_params / 819e9
                print(f'{backend:6s} steps={num_steps:2d} tw={top_window:2d}'
                      f' unroll={int(unroll)}:'
                      f' {best*1e3:7.1f} ms'
                      f' ({best/num_steps*1e3:6.2f} ms/step,'
                      f' {batch*num_steps/best:7.0f} tok/s,'
                      f' floor {floor*1e3:5.0f} ms, x{best/floor:4.1f})',
                      flush=True)
            except Exception as exc:
                print(f'{backend:6s} steps={num_steps:2d} tw={top_window:2d}'
                      f' unroll={int(unroll)}:'
                      f' FAILED {repr(exc)[:200]}', flush=True)


if __name__ == '__main__':
    main()
