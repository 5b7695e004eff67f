#!/usr/bin/env python3
"""Calibrate ``reference_ouro``'s limits on the chip (PR 48), at the
benchmark configuration's widths against the float32 reference, with the
wrong programs the limits have to catch.

    chiprun -- python scripts/probe_ouro_reference.py [config.json] [--arms a,b] [seed ...]

Each arm is the cell's own check (``ouro_closed.sample_for_check`` and
``verify``: the greedy call through ``LLMEngine``, then the reference's full
forward pass) on an engine built as the arm says; one JSON line an arm.
Arms: ``program`` (as served); ``three_passes`` (the stack run three times
in place of four, over a pool of all 192 planes); ``shared_planes`` (pass
``t`` writes and reads pass 0's planes: the shared-cache approximation of
the paper); ``no_norm_between`` (the final norm left out between the passes:
a pass starts from the stack's raw output); ``int8_kv`` (every K and V row
rounded to int8, one scale a token and head, before it enters the pool: the
nearest precision below the one the pool states).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / 'scripts')]  # the neighbours below

import jax

from benchmarks.drivers import ouro_closed
from probe_deepseek_reference import patched
from probe_lfm2_reference import _int8_writer
from distllm_tpu.models import common, mistral, ouro
from distllm_tpu.ops import paged_attention
from distllm_tpu.utils import enable_compile_cache

ARMS = 'program,three_passes,shared_planes,no_norm_between,int8_kv'


class _FewerPasses(ouro.OuroConfig):
    """Runs ``total_ut_steps`` passes over a pool that has the planes of
    ``pool_passes``: what the check reads of the last pass is then a plane
    that nothing wrote."""

    pool_passes: int = 4

    def cache_spec(self) -> common.CacheSpec:
        spec = super().cache_spec()
        planes = self.num_layers * self.pool_passes
        return dataclasses.replace(
            spec, paged=(common.PagedGroup('kv', planes),)
        )


def _on_pass_zero(layer):
    def shared(cfg, rope, attn_backend, rows, carry, lp, plane, window_l, **kw):
        return layer(
            cfg, rope, attn_backend, rows, carry, lp, plane % cfg.num_layers,
            window_l, **kw,
        )

    return shared


def _no_norm_between(params, cfg, t, x, state, pick=None,
                     close=ouro._close_pass):
    _, state, g = close(params, cfg, t, x, state, pick)
    return x, state, g


def arm(cfg, name: str):
    """``(config the program is built with, [(module, attribute, wrong
    value)])`` of an arm; the reference always gets the file's config."""
    if name == 'three_passes':
        fields = cfg.model_dump()
        cfg = _FewerPasses(**{
            **fields, 'total_ut_steps': cfg.total_ut_steps - 1,
            'pool_passes': cfg.total_ut_steps,
        })
    patches = {
        'shared_planes': [
            (mistral, name_, _on_pass_zero(getattr(mistral, name_)))
            for name_ in ('_span_layer', '_token_layer')
        ],
        'no_norm_between': [(ouro, '_close_pass', _no_norm_between)],
        # The model's programs import these when they are traced.
        'int8_kv': [
            (paged_attention, name_,
             _int8_writer(getattr(paged_attention, name_)))
            for name_ in ('write_chunk_kv', 'write_token_kv')
        ],
    }
    return cfg, patches.get(name, [])


def _workload(model: dict) -> dict:
    cell = next(ROOT.glob('benchmarks/workloads/ouro-*.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    return workload


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    workload = _workload(model)
    as_served = ouro_closed._model_cfg
    for seed in seeds:
        for name in arms:
            ctx = SimpleNamespace(
                config=model, seed=seed, workload=workload,
                traffic=workload['traffic'],
                rehearsal=jax.devices()[0].platform != 'tpu',
            )
            cfg, patches = arm(as_served(model), name)
            ouro_closed._model_cfg = lambda m, cfg=cfg: cfg
            try:
                with patched(patches):
                    state = ouro_closed.build(ctx)
                    seconds = ouro_closed.sample_for_check(state, ctx)
            finally:
                ouro_closed._model_cfg = as_served
            correct, detail = ouro_closed.verify(state, ctx, {'failed': 0})
            for key in ('kernel_call_s', 'scope_s', 'setup_split_s', 'window_engine'):
                detail.pop(key)
            print(json.dumps({
                'seed': seed, 'arm': name,
                'device': jax.devices()[0].device_kind, 'correct': correct,
                'check_s': round(seconds, 1), **detail,
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
    config = ROOT / 'benchmarks/configs/ouro-2.6b.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    seeds = [int(a) for a in args] or [4800048001]
    check(json.loads(config.read_text()), seeds, opts.arms.split(','))
    return 0


if __name__ == '__main__':
    sys.exit(main())
