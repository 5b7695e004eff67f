#!/usr/bin/env python3
"""Calibrate ``reference_smallthinker``'s limits on the chip (PR 52, as PR 30
did for laguna), at the benchmark configuration's widths against the float32
reference, with the wrong programs the limits have to catch.

    chiprun -- python scripts/probe_smallthinker_reference.py [config.json] [--arms a,b] [seed ...]

Each arm is the cell's own check (``smallthinker_closed.sample_for_check``
and ``verify``: the greedy call at the cell's load through ``LLMEngine``,
then the reference) on an engine built as the arm says; one JSON line an
arm. Arms: ``program`` (as served); ``router_after_attention`` (the router
fed the post-attention norm's output, the usual placement); ``rope_in_full``
(RoPE applied in the full layers too); ``silu`` (SiLU for ReLU on the
experts' gate); ``window_2048`` (the window half as long, mask and allocator
alike); ``int8_kv`` (the nearest precision below the one the configuration
states: every K and V row rounded to int8 before it enters the pools, one
scale a token and KV head; the engine refuses a real int8 pool beside a
windowed group). ``--arms ladder [--rungs 40x22000,24x22000]`` walks the slot and pool ladder instead:
one timed call after a warm-up call a rung (``rows x blocks``: tokens/s, the
chip's peak bytes, rows a decode window, deferrals, preemptions).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax
import jax.numpy as jnp

from benchmarks.drivers import _engine, engine_closed, smallthinker_closed
from distllm_tpu.models import moe, smallthinker
from distllm_tpu.ops import paged_attention
from distllm_tpu.utils import enable_compile_cache

ARMS = 'program,router_after_attention,rope_in_full,silu,window_2048,int8_kv'
LADDER = ((48, 19000), (40, 22000), (24, 22000))


def _int8_rows(rows):
    """``rows [..., N_kv, Hd]`` as an int8 pool would hand them back: 255
    levels, one scale a token and KV head."""
    scale = (
        jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)
        / paged_attention.KV_QUANT_MAX
    )
    q = paged_attention.quantize_kv_rows(rows, scale)  # the int8 pool's own
    return (q.astype(jnp.float32) * scale[..., None]).astype(rows.dtype)


def _int8_writer(write):
    def rounded(k_pool, v_pool, k, v, *rest, **kw):
        return write(k_pool, v_pool, _int8_rows(k), _int8_rows(v), *rest, **kw)

    return rounded


def _silu_experts(*args, **kw):
    return moe.routed_experts(*args, **{**kw, 'activation': 'silu'})


def _arm(cfg, arm: str):
    """``(config the program is built with, [(module, attribute, wrong
    value)])`` of an arm; the reference always gets the file's config."""
    updates = {
        'rope_in_full': {'rope_layout': (1,) * cfg.num_layers},
        'window_2048': {'sliding_window': cfg.sliding_window // 2},
    }
    patches = {
        # No ranking made ahead: ``routed_experts`` ranks from its own rows.
        'router_after_attention': [(smallthinker, '_rank', lambda *a: None)],
        'silu': [(smallthinker, 'routed_experts', _silu_experts)],
        # The model's programs import the writers when they are traced.
        'int8_kv': [
            (paged_attention, name, _int8_writer(getattr(paged_attention, name)))
            for name in ('write_chunk_kv', 'write_token_kv')
        ],
    }
    return cfg.model_copy(update=updates.get(arm, {})), patches.get(arm, [])


def _ctx(model: dict, seed: int):
    cell = next(ROOT.glob('benchmarks/workloads/smallthinker-*.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    return SimpleNamespace(
        config=model, seed=seed, workload=workload, traffic=workload['traffic'],
        rehearsal=jax.devices()[0].platform != 'tpu',
    )


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    as_served = smallthinker_closed._model_cfg
    for seed in seeds:
        for arm in arms:
            cfg, patches = _arm(as_served(model), arm)
            smallthinker_closed._model_cfg = lambda m, cfg=cfg: cfg
            saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
            for mod, name, wrong in patches:
                setattr(mod, name, wrong)
            ctx = _ctx(model, seed)
            state = smallthinker_closed.build(ctx)
            seconds = smallthinker_closed.sample_for_check(state, ctx)
            for mod, name, right in saved:
                setattr(mod, name, right)
            smallthinker_closed._model_cfg = as_served
            correct, detail = smallthinker_closed.verify(state, ctx, {'failed': 0})
            for key in ('kv_pools', 'moe_form', 'setup_split_s', 'window_engine'):
                detail.pop(key)
            print(json.dumps({
                'seed': seed, 'arm': arm, 'device': jax.devices()[0].device_kind,
                'correct': correct, 'check_s': round(seconds, 1), **detail,
            }), flush=True)


def ladder(model: dict, seed: int, rungs=LADDER) -> None:
    """One timed call behind a warm-up call at each rung (``memory_peak_bytes``
    is the process's: the largest rung's so far)."""
    for rows, blocks in rungs:
        rung = json.loads(json.dumps(model))
        rung['engine'].update(max_num_seqs=rows, num_blocks=blocks)
        ctx = _ctx(rung, seed)
        state = smallthinker_closed.build(ctx)
        engine = state['engine']
        budget = int(ctx.traffic['output_tokens']['value'])
        sampling = _engine.sampling(ctx, budget)
        engine.generate_ids(engine_closed._call_prompts(ctx, 'warmup0'), sampling)
        before = engine.flight.total_recorded
        t = time.perf_counter()
        outputs = engine.generate_ids(
            engine_closed._call_prompts(ctx, 'call0'), sampling
        )
        seconds = time.perf_counter() - t
        flight = _engine.flight_since(engine, before)
        peak = (jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')
        print(json.dumps({
            'rows': rows, 'blocks': blocks, 'seed': seed,
            'gen_tok_s': sum(len(o) for o in outputs) / seconds,
            'call_s': seconds, 'memory_peak_bytes': peak,
            'rows_a_window': max(
                (r['batch'] for r in flight if r['kind'] == 'decode'), default=0
            ),
            'preemptions': sum(r['kind'] == 'preempt' for r in flight),
            'budget_deferrals': engine.telemetry.get('budget_deferrals', 0),
        }), flush=True)
        del engine  # the last name that holds it: its pools go with it
        smallthinker_closed.close(state)


def main() -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument('args', nargs='*', metavar='config.json | seed')
    parser.add_argument('--arms', default=ARMS)
    parser.add_argument('--rungs', default=None, metavar='ROWSxBLOCKS,...')
    opts = parser.parse_intermixed_args()
    enable_compile_cache()
    args = opts.args
    config = ROOT / 'benchmarks/configs/smallthinker-21b-a3b.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    seeds = [int(a) for a in args] or [3100000019]
    model = json.loads(config.read_text())
    if opts.arms == 'ladder':
        rungs = LADDER if opts.rungs is None else tuple(
            tuple(int(n) for n in rung.split('x'))
            for rung in opts.rungs.split(',')
        )
        ladder(model, seeds[0], rungs)
    else:
        check(model, seeds, opts.arms.split(','))
    return 0


if __name__ == '__main__':
    sys.exit(main())
