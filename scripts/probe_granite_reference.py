#!/usr/bin/env python3
"""Calibrate the two limits of ``benchmarks/reference_granite`` on the chip
(PR 26, as PR 21 did for Mistral), at the benchmark configuration's widths
against the float32 reference, with faults the limits have to catch.

    chiprun -- python scripts/probe_granite_reference.py check [config.json] [--arms a,b] [seed ...]
    chiprun -- python scripts/probe_granite_reference.py logits [config.json] [seed ...]

``check`` is the cell's own check (``granite_closed.sample_for_check`` and
``verify``: the greedy call at the cell's load through ``LLMEngine``, then
the reference) on an engine built as the arm says; one JSON line an arm with
what ``verify`` limits: the largest token gap and, per Mamba layer, the
error of the SSM state the check rows left in the pool. Arms: ``program``
(as served), ``bf16_state`` (a build whose state pool keeps the SSM state
in bfloat16: the precision below the one the configuration states),
``sqrt_scale`` (scores times 1/sqrt(128) instead of
``attention_multiplier``).

``logits`` goes under the engine, for the logits it does not give: for every
seed and prompt, prefill in 512-token spans through
``granite_hybrid.prefill_paged``, then greedy decode steps through the
model's own decode step, state pool and paged KV as the engine holds them
(arms as above, ``bf16_state`` rounding the state after every span and
step). One JSON line an arm: relative RMS of the logit differences (over the
reference logits' standard deviation), the largest single difference over
that RMS, and the largest token gap (``reference.token_gaps``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference, reference_granite
from benchmarks.drivers import granite_closed
from distllm_tpu.generate.engine.kv_cache import StatePool
from distllm_tpu.models import granite_hybrid as gh
from distllm_tpu.utils import enable_compile_cache

PROMPT_TOKENS = (48, 55, 63, 71, 77, 86, 100, 700)
OUTPUT_TOKENS = 16
SPAN = 512
BLOCK = 16


def program_logits(params, cfg, prompt, backend, round_state):
    """Logits of the ``OUTPUT_TOKENS`` greedy steps after ``prompt`` and the
    tokens taken, through the paged prefill and the decode step."""
    blocks = (len(prompt) + OUTPUT_TOKENS) // BLOCK + 2
    shape = (cfg.num_paged_layers, blocks + 1, BLOCK,
             cfg.num_kv_heads * cfg.head_size)
    k, v = (jnp.zeros(shape, jnp.dtype(cfg.dtype)) for _ in range(2))
    table = jnp.arange(1, blocks + 1, dtype=jnp.int32)[None]
    state = StatePool(cfg.state_spec(), 1).state
    slots = jnp.zeros((1,), jnp.int32)

    def rounded(state):
        if not round_state:
            return state
        return {**state, 'ssm': tuple(
            s.astype(jnp.bfloat16).astype(jnp.float32) for s in state['ssm']
        )}

    prefill = jax.jit(
        lambda p, ids, pos, k, v, ctx, tails, state: gh.prefill_paged(
            p, cfg, ids, pos, k, v, table, ctx, tails, state, slots,
            attn_backend=backend,
        ), donate_argnums=(3, 4, 7),
    )
    step = jax.jit(
        lambda p, ids, pos, k, v, ctx, state: gh._decode_core(
            p, cfg, backend, ids, pos, ctx, (k, v, state), table,
            jnp.ones((1,), bool),
        )[:2], donate_argnums=(3, 4, 6),
    )
    start = 0
    while start < len(prompt):
        n = min(SPAN, len(prompt) - start)
        bucket = max(16, 1 << (n - 1).bit_length())
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt[start:start + n]
        pos = (start + np.arange(bucket, dtype=np.int32))[None]
        logits, k, v, state = prefill(
            params, ids, pos, k, v, np.asarray([start + n], np.int32),
            np.asarray([n], np.int32), state,
        )
        state = rounded(state)
        start += n
    rows, tokens = [np.asarray(logits[0])], []
    for i in range(OUTPUT_TOKENS):
        tokens.append(int(rows[-1].argmax()))
        if i + 1 == OUTPUT_TOKENS:
            break
        at = len(prompt) + i
        logits, (k, v, state) = step(
            params, np.asarray([tokens[-1]], np.int32),
            np.asarray([at], np.int32), k, v,
            np.asarray([at + 1], np.int32), state,
        )
        state = rounded(state)
        rows.append(np.asarray(logits[0]))
    return np.stack(rows), tokens


def logits(model: dict, seeds: list[int], arms: list[str]) -> None:
    backend = 'pallas' if jax.devices()[0].platform == 'tpu' else 'xla'
    cfg = granite_closed._model_cfg(model)
    as_arm = _arm_cfgs(cfg)
    arms = {
        arm: (cfg if arm == 'bf16_state' else as_arm[arm], arm == 'bf16_state')
        for arm in arms
    }
    for seed in seeds:
        ctx = type('Ctx', (), {'config': model, 'seed': seed})()
        params = granite_closed._weights(ctx)
        rng = np.random.default_rng(seed)
        prompts = [
            [int(t) for t in rng.integers(0, model['vocab_size'], n)]
            for n in PROMPT_TOKENS
        ]
        for arm, (arm_cfg, round_state) in arms.items():
            rel_rms, max_over_rms, gaps = [], [], []
            for prompt in prompts:
                got, tokens = program_logits(
                    params, arm_cfg, prompt, backend, round_state
                )
                ids = np.asarray([prompt + tokens], np.int32)
                want = reference_granite.granite_logits(params, model, ids)
                gaps.extend(reference.token_gaps(want, [len(prompt)], [tokens]))
                want = np.asarray(
                    want[0, len(prompt) - 1: len(prompt) - 1 + len(tokens)]
                )
                for g, w in zip(got, want):
                    diff = g - w
                    rms = float(np.sqrt((diff ** 2).mean()))
                    rel_rms.append(rms / float(w.std()))
                    max_over_rms.append(float(np.abs(diff).max()) / rms)
            gaps.sort()
            print(json.dumps({
                'seed': seed, 'arm': arm, 'device': jax.devices()[0].device_kind,
                'rel_rms_max': max(rel_rms), 'rel_rms_mean': float(np.mean(rel_rms)),
                'max_over_rms': max(max_over_rms), 'token_gap_max_std': max(gaps),
                'token_gaps_largest': [round(g, 4) for g in gaps[-6:]],
                'flipped': sum(g > 0 for g in gaps), 'positions': len(gaps),
            }), flush=True)
        del params


def _arm_cfgs(cfg) -> dict:
    class Bf16State(type(cfg)):
        def state_spec(self):
            spec = super().state_spec()
            return {**spec, 'ssm': tuple(
                jax.ShapeDtypeStruct(s.shape, jnp.bfloat16) for s in spec['ssm']
            )}

    return {
        'program': cfg,
        'bf16_state': Bf16State(**cfg.model_dump()),
        'sqrt_scale': cfg.model_copy(
            update={'attention_multiplier': cfg.head_size ** -0.5}),
    }


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    from types import SimpleNamespace

    cell = next(ROOT.glob('benchmarks/workloads/granite-*.batch_generate.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    as_served = granite_closed._model_cfg
    for seed in seeds:
        for arm in arms:
            granite_closed._model_cfg = lambda m, arm=arm: _arm_cfgs(as_served(m))[arm]
            ctx = SimpleNamespace(
                config=model, seed=seed, workload=workload,
                traffic=workload['traffic'],
                rehearsal=jax.devices()[0].platform != 'tpu',
            )
            state = granite_closed.build(ctx)
            seconds = granite_closed.sample_for_check(state, ctx)
            correct, detail = granite_closed.verify(state, ctx, {'failed': 0})
            print(json.dumps({
                'seed': seed, 'arm': arm, 'device': jax.devices()[0].device_kind,
                'correct': correct, 'check_s': round(seconds, 1), **detail,
            }), flush=True)
    granite_closed._model_cfg = as_served


def main() -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument('mode', choices=('check', 'logits'))
    parser.add_argument('args', nargs='*', metavar='config.json | seed')
    parser.add_argument('--arms', default='program,bf16_state,sqrt_scale')
    opts = parser.parse_intermixed_args()
    enable_compile_cache()
    args = opts.args
    config = ROOT / 'benchmarks/configs/granite-4.0-h-small.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    arms = opts.arms.split(',')
    seeds = [int(a) for a in args] or [3100000019]
    model = json.loads(config.read_text())
    {'check': check, 'logits': logits}[opts.mode](model, seeds, arms)
    return 0


if __name__ == '__main__':
    sys.exit(main())
