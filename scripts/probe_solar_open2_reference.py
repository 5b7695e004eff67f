#!/usr/bin/env python3
"""Calibrate ``reference_solar_open2``'s limits on the chip (PR 45, as PR 41
did for falcon_h1), at the benchmark configuration's widths against the
float32 reference, with the wrong programs the limits have to catch, and walk
the slot ladder that settles ``max_num_seqs``.

    chiprun -- python scripts/probe_solar_open2_reference.py [config.json] [--arms a,b] [seed ...]

Each arm but ``ladder`` is the cell's own check
(``solar_open2_closed.sample_for_check`` and ``verify``: the greedy calls at
the cell's load through ``LLMEngine``, then the reference) on an engine
built as the arm says; one JSON line an arm. Arms: ``program`` (as served);
``state_bf16`` (the matrix state rounded to bfloat16 whenever it is written:
the nearest precision below the one the state pool states);
``span_default_precision`` (the span form's products at the TPU's default:
float32 operands rounded to bfloat16, a float32 result); ``beta_half``
(beta without its factor 2: sigmoid alone); ``scalar_decay`` (one decay a
head, the mean of its channels', in place of a decay a channel);
``softmax_scoring`` (the router's gates a softmax over the kept logits, no
selection bias, in place of sigmoid scores over their sum); ``ladder`` (no
check: for 64, 96 and 128 slots an engine, one warm-up call and one timed
call of the cell's traffic: ``gen_tok_s`` and the device's peak bytes).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / 'scripts')]  # the neighbours below

import jax
import jax.numpy as jnp

from benchmarks.drivers import _engine, engine_closed, solar_open2_closed
from probe_deepseek_reference import patched
from distllm_tpu.models import moe, solar_open2
from distllm_tpu.ops import kda
from distllm_tpu.utils import enable_compile_cache

ARMS = (
    'program,state_bf16,span_default_precision,beta_half,scalar_decay,'
    'softmax_scoring'
)
LADDER = (64, 96, 128)


def _bf16_state(form):
    def rounded(*args, **kw):
        out, state = form(*args, **kw)
        # a pair of converts is a round trip the compiler may drop
        return out, jax.lax.reduce_precision(state, 8, 7)

    return rounded


def _beta_half(u, lp, cfg, conv0, inputs=solar_open2._kda_inputs):
    q, k, v, g, beta, window = inputs(u, lp, cfg, conv0)
    return q, k, v, g, beta / 2.0, window


def _scalar_decay(u, lp, cfg, conv0, inputs=solar_open2._kda_inputs):
    q, k, v, g, beta, window = inputs(u, lp, cfg, conv0)
    g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    return q, k, v, g, beta, window


def _softmax_scoring(*args, routed=moe.routed_experts, **kw):
    kw.update(scoring='softmax', select_bias=None)
    return routed(*args, **kw)


def arm(name: str) -> list:
    """``[(module, attribute, wrong value)]`` of an arm."""
    return {
        'state_bf16': [
            (kda, form, _bf16_state(getattr(kda, form)))
            for form in ('kda_span', 'kda_step')
        ],
        'span_default_precision': [
            (kda, '_EXACT', jax.lax.Precision.DEFAULT)
        ],
        'beta_half': [(solar_open2, '_kda_inputs', _beta_half)],
        'scalar_decay': [(solar_open2, '_kda_inputs', _scalar_decay)],
        'softmax_scoring': [(solar_open2, 'routed_experts', _softmax_scoring)],
    }.get(name, [])


def _workload(model: dict) -> dict:
    cell = next(ROOT.glob('benchmarks/workloads/solar-open2-*.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    return workload


def _ctx(model: dict, seed: int, workload: dict):
    return SimpleNamespace(
        config=model, seed=seed, workload=workload,
        traffic=workload['traffic'],
        rehearsal=jax.devices()[0].platform != 'tpu',
    )


def ladder(model: dict, seed: int, workload: dict, head: dict) -> None:
    """One engine a rung, smallest first (the device's peak only grows)."""
    budget = int(workload['traffic']['output_tokens']['value'])
    for slots in LADDER:
        rung = dict(model, engine=dict(model['engine'], max_num_seqs=slots))
        ctx = _ctx(rung, seed, workload)
        t0 = time.perf_counter()
        state = solar_open2_closed.build(ctx)
        engine = state['engine']
        t1 = time.perf_counter()
        seconds = []
        for call in ('warmup0', 'call0'):
            prompts = engine_closed._call_prompts(ctx, call)
            t = time.perf_counter()
            outputs = engine.generate_ids(prompts, _engine.sampling(ctx, budget))
            seconds.append(time.perf_counter() - t)
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            **head, 'slots': slots,
            'gen_tok_s': sum(len(o) for o in outputs) / seconds[1],
            'call_s': [round(s, 2) for s in seconds],
            'build_s': round(t1 - t0, 1),
            'memory_peak_bytes': stats.get('peak_bytes_in_use'),
            'moe_form': state['moe_form'],
        }), flush=True)
        solar_open2_closed.close(state)
        del state, engine


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    workload = _workload(model)
    for seed in seeds:
        for name in arms:
            ctx = _ctx(model, seed, workload)
            head = {
                'seed': seed, 'arm': name,
                'device': jax.devices()[0].device_kind,
            }
            if name == 'ladder':
                ladder(model, seed, workload, head)
                continue
            # the check too: it runs the recurrence's two forms from equal
            # operands, and has to run the arm's
            with patched(arm(name)):
                state = solar_open2_closed.build(ctx)
                seconds = solar_open2_closed.sample_for_check(state, ctx)
                correct, detail = solar_open2_closed.verify(
                    state, ctx, {'failed': 0}
                )
            for key in (
                'kv_pools', 'state_pool', 'kernel_call_s', 'scope_s',
                'setup_split_s', 'moe_form', 'moe_grouped_tiles',
            ):
                detail.pop(key)
            print(json.dumps({
                **head, 'correct': correct, 'greedy_s': round(seconds, 1),
                **detail,
            }), flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument('args', nargs='*', metavar='config.json | seed')
    parser.add_argument('--arms', default=ARMS)
    opts = parser.parse_intermixed_args()
    enable_compile_cache()
    args = opts.args
    config = ROOT / 'benchmarks/configs/solar-open2-250b.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    seeds = [int(a) for a in args] or [3200000023]
    check(json.loads(config.read_text()), seeds, opts.arms.split(','))
    return 0


if __name__ == '__main__':
    sys.exit(main())
