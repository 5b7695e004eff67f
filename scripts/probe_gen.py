"""Generation-loop breakdown on the real chip.

A 2026-07-31 record on older code (in git history; not re-measured, a
hypothesis) had bf16 7B at batch 32 at 605 tok/s against a
~1,800 tok/s weight-bandwidth roofline (14.5 GB reads / 819 GB/s * batch
32 * 16-step window => >=283 ms/window floor). This instruments the
pipelined loop to see where the other ~550 ms/window goes: host-side
window planning (numpy input builds + device_put), dispatch gaps, or the
token fetch. Small mode (DISTLLM_BENCH_SMALL=1) runs tiny dims on CPU to
keep the instrumentation itself tested.
"""

from __future__ import annotations

import os
import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))

import time


import jax
import numpy as np

from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.models import mistral


def main() -> None:
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    if small:
        model_cfg = mistral.MistralConfig(
            vocab_size=2048, hidden_size=256, num_layers=4, num_heads=8,
            num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
        )
        engine_cfg = EngineConfig(
            block_size=16, num_blocks=128, max_num_seqs=8, max_model_len=256,
            decode_steps=8, pipeline_depth=2,
        )
        n_prompts, gen_tokens = 16, 32
    else:
        model_cfg = mistral.MistralConfig(dtype='bfloat16')
        engine_cfg = EngineConfig(
            block_size=16, num_blocks=712, max_num_seqs=32, max_model_len=512,
            decode_steps=16, pipeline_depth=2, attn_backend='pallas',
        )
        n_prompts, gen_tokens = 96, 128

    params = mistral.init_on_device(jax.random.PRNGKey(0), model_cfg)

    class _Tok:
        eos_id = None

    engine = LLMEngine(model_cfg, params, _Tok(), engine_cfg, own_params=True)
    engine.warmup()

    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, model_cfg.vocab_size, size=int(n)))
        for n in rng.integers(32, 192, size=n_prompts)
    ]
    sampling = SamplingParams(
        temperature=0.5, top_p=0.95, min_p=0.1, max_tokens=gen_tokens
    )

    # Wrap the loop's phases with timers.
    stats = {'dispatch_s': 0.0, 'fetch_s': 0.0, 'n_fetch': 0}
    orig_dispatch = engine._dispatch_window
    orig_process = engine._process_window

    def timed_dispatch(carried):
        t0 = time.perf_counter()
        out = orig_dispatch(carried)
        stats['dispatch_s'] += time.perf_counter() - t0
        return out

    def timed_process(window):
        t0 = time.perf_counter()
        out = orig_process(window)
        stats['fetch_s'] += time.perf_counter() - t0
        stats['n_fetch'] += 1
        return out

    engine._dispatch_window = timed_dispatch
    engine._process_window = timed_process

    start = time.perf_counter()
    outs = engine.generate_ids(prompts, sampling)
    elapsed = time.perf_counter() - start
    n_tokens = sum(len(o) for o in outs)

    t = engine.telemetry
    windows = t.get('decode_windows', 0)
    print(f'tok/s: {n_tokens / elapsed:.1f}  ({n_tokens} tokens in {elapsed:.2f}s)')
    print(f'windows: {windows}  prefills: {t.get("prefill_dispatches")}  '
          f'overshoot: {t.get("overshoot_frac")}')
    if windows:
        print(f'per-window: total {elapsed / windows * 1e3:.1f} ms | '
              f'host dispatch {stats["dispatch_s"] / windows * 1e3:.1f} ms | '
              f'fetch wait {stats["fetch_s"] / max(1, stats["n_fetch"]) * 1e3:.1f} ms')
    # Shape metadata survives donation, so count from the live tree.
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(params)
    )
    bw = 819e9 if not small else None
    if bw:
        floor_s = engine_cfg.decode_steps * 2 * n_params / bw
        print(f'roofline window floor {floor_s * 1e3:.0f} ms '
              f'(weights {2 * n_params / 1e9:.1f} GB x {engine_cfg.decode_steps} steps @ 819 GB/s)')


if __name__ == '__main__':
    main()
