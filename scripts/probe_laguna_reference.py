#!/usr/bin/env python3
"""Calibrate ``reference_laguna``'s two token-gap limits on the chip (PR 30, as
PR 26 did for Granite), at the benchmark configuration's widths against the
float32 reference, with the wrong programs the limits have to catch.

    chiprun -- python scripts/probe_laguna_reference.py [config.json] [--arms a,b] [seed ...]

Each arm is the cell's own check (``laguna_closed.sample_for_check`` and
``verify``: the greedy call at the cell's load through ``LLMEngine``, then
the reference) on an engine built as the arm says; one JSON line an arm.
Arms: ``program`` (as served); ``window_496`` and ``window_528`` (the window
a block short and a block long, mask and allocator alike); ``int8_kv`` (the
nearest precision below the one the configuration states: every K and V row
rounded to int8 before it enters the pools, one scale a token and KV head,
finer than ``QuantizedKV``'s one a block and head, so an int8 pool at its
best; the engine refuses a real one beside a windowed group);
``freed_block`` (the allocator gives back a block a query still sees: its
table entry is the trash block); ``no_gate`` (the attention gate left out);
``no_yarn`` (plain RoPE in the full layers); ``rotate_all`` (all 128 dims
rotated in the full layers); ``no_scale`` (the routed scale 2.5 left out).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax
import jax.numpy as jnp

from benchmarks.drivers import laguna_closed
from distllm_tpu.generate.engine import kv_cache
from distllm_tpu.models import common, laguna
from distllm_tpu.ops import paged_attention
from distllm_tpu.utils import enable_compile_cache

ARMS = (
    'program,window_496,window_528,int8_kv,freed_block,no_gate,no_yarn,'
    'rotate_all,no_scale'
)


def _no_gate(attn, normed, lp, cfg, kind):
    return common.dense(
        attn.reshape(*attn.shape[:-2], cfg.num_heads(kind) * cfg.head_dim),
        lp['o']['kernel'],
    )


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


class _FreesABlockEarly(kv_cache.WindowBlocks):
    def first_visible_block(self, position):
        return super().first_visible_block(position) + 1


def _full_rope(cfg, **over):
    rope = json.loads(json.dumps(cfg.rope_parameters))
    rope['full'].update(over)
    return {'rope_parameters': rope}


def _arm(cfg, arm: str, block: int):
    """``(config the program is built with, {module attribute: wrong
    value})`` of an arm; the reference always gets the file's config.
    ``block``: the engine's block size, what a window is wrong by."""
    updates = {
        'program': {}, 'freed_block': {}, 'no_gate': {}, 'int8_kv': {},
        'window_496': {'sliding_window': cfg.sliding_window - block},
        'window_528': {'sliding_window': cfg.sliding_window + block},
        'no_yarn': _full_rope(cfg, rope_type='default'),
        'rotate_all': _full_rope(cfg, partial_rotary_factor=1.0),
        'no_scale': {'routed_scaling_factor': 1.0},
    }
    patches = {
        'freed_block': [(kv_cache, 'WindowBlocks', _FreesABlockEarly)],
        'no_gate': [(laguna, '_attn_out', _no_gate)],
        # The model's programs import the writers when they are traced.
        'int8_kv': [
            (paged_attention, name, _int8_writer(getattr(paged_attention, name)))
            for name in ('write_chunk_kv', 'write_token_kv')
        ],
    }
    return cfg.model_copy(update=updates[arm]), patches.get(arm, [])


def _workload(model: dict) -> dict:
    cell = next(ROOT.glob('benchmarks/workloads/laguna-*.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    return workload


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    from distllm_tpu.generate.engine import engine as engine_mod

    workload = _workload(model)
    as_served = laguna_closed._model_cfg
    for seed in seeds:
        for arm in arms:
            cfg, patches = _arm(
                as_served(model), arm, model['engine']['block_size']
            )
            laguna_closed._model_cfg = lambda m, cfg=cfg: cfg
            saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
            for mod, name, wrong in patches:
                setattr(mod, name, wrong)
                if mod is kv_cache:  # the engine imported the name
                    setattr(engine_mod, name, wrong)
            ctx = SimpleNamespace(
                config=model, seed=seed, workload=workload,
                traffic=workload['traffic'],
                rehearsal=jax.devices()[0].platform != 'tpu',
            )
            state = laguna_closed.build(ctx)
            seconds = laguna_closed.sample_for_check(state, ctx)
            for mod, name, right in saved:
                setattr(mod, name, right)
                if mod is kv_cache:
                    setattr(engine_mod, name, right)
            correct, detail = laguna_closed.verify(state, ctx, {'failed': 0})
            detail.pop('kv_pools')
            print(json.dumps({
                'seed': seed, 'arm': arm, 'device': jax.devices()[0].device_kind,
                'correct': correct, 'check_s': round(seconds, 1), **detail,
            }), flush=True)
    laguna_closed._model_cfg = as_served


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
    config = ROOT / 'benchmarks/configs/laguna-xs.2.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    seeds = [int(a) for a in args] or [3100000019]
    check(json.loads(config.read_text()), seeds, opts.arms.split(','))
    return 0


if __name__ == '__main__':
    sys.exit(main())
