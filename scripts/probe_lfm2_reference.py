#!/usr/bin/env python3
"""Calibrate ``reference_lfm2``'s limits on the chip (PR 39, as PR 32 did for
kanana), at the benchmark configuration's widths against the float32
reference, with the wrong programs the limits have to catch.

    chiprun -- python scripts/probe_lfm2_reference.py [config.json] [--arms a,b] [seed ...]

Each arm is the cell's own check (``lfm2_closed.sample_for_check`` and
``verify``: the greedy call at the cell's load through ``LLMEngine``, then
the reference) on an engine built as the arm says; one JSON line an arm.
Arms: ``program`` (as served); ``no_bias`` (the selection bias dropped);
``no_qk_norm`` (``q_layernorm`` and ``k_layernorm`` dropped); ``state_fp8``
(the conv state rounded to float8 e4m3 as it is written: the nearest
precision below the one the state pool states); ``state_late`` (the conv
state carried a token late); ``int8_kv`` (the nearest precision below the
one the K/V pool states: every K and V row rounded to int8, one scale a
token and head, before it enters the pool, an int8 pool at its best; the
kernel refuses a real one at 64-wide heads); ``experts_16_31`` (the 16 held
experts taken as ids 16-31 of the router's 32); ``one_row`` (the program as
served, and ONE scored row reads the slot and the pages of the row scored
after it, as a wrong slot or a wrong entry of one block table would).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / 'scripts')]  # the neighbours below

import jax
import jax.numpy as jnp

from benchmarks.drivers import lfm2_closed
from probe_deepseek_reference import patched
from distllm_tpu.models import common, lfm2
from distllm_tpu.ops import paged_attention
from distllm_tpu.utils import enable_compile_cache

ARMS = (
    'program,no_bias,no_qk_norm,state_fp8,state_late,int8_kv,experts_16_31,'
    'one_row'
)


def _no_bias(*args, select_bias=None, routed=lfm2.routed_experts, **kw):
    return routed(*args, select_bias=None, **kw)


def _no_qk_norm(normed, lp, cfg, cos, sin, positions):
    heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg.head_size)  # noqa: E731
    q = heads(common.dense(normed, lp['q']['kernel']), cfg.num_heads)
    k = heads(common.dense(normed, lp['k']['kernel']), cfg.num_kv_heads)
    v = heads(common.dense(normed, lp['v']['kernel']), cfg.num_kv_heads)
    return (
        common.apply_rope(q, cos, sin, positions),
        common.apply_rope(k, cos, sin, positions), v,
    )


def _fp8(conv):
    """Rounded to float8 e4m3's 4 exponent and 3 mantissa bits (a pair of
    converts is a round trip the compiler may drop as excess precision:
    the first run of this arm read the program's numbers)."""
    return jax.lax.reduce_precision(conv, exponent_bits=4, mantissa_bits=3)


def _state_fp8_span(h, lp, conv0, tail_lens, span=lfm2.conv_span):
    out, conv = span(h, lp, conv0, tail_lens)
    return out, _fp8(conv)


def _state_fp8_step(h, lp, conv0, live, step=lfm2.conv_step):
    out, conv = step(h, lp, conv0, live)
    return out, _fp8(conv)


def _state_late_span(h, lp, conv0, tail_lens, span=lfm2.conv_span):
    out, _ = span(h, lp, conv0, tail_lens)
    return out, span(h, lp, conv0, jnp.maximum(tail_lens - 1, 0))[1]


def _state_late_step(h, lp, conv0, live, step=lfm2.conv_step):
    # the state after the step is the one before it moved up by nothing:
    # the row the step brought is dropped, the oldest kept
    return step(h, lp, conv0, live)[0], conv0


def _int8_rows(rows):
    """``rows [..., kv heads, d]`` as an int8 pool would hand them back:
    255 levels, one scale a token and head."""
    scale = (
        jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)
        / paged_attention.KV_QUANT_MAX
    )
    q = paged_attention.quantize_kv_rows(rows, scale)  # the int8 pool's own
    return (q.astype(jnp.float32) * scale[..., None]).astype(rows.dtype)


def _int8_writer(write):
    def rounded(k_cache, v_cache, new_k, new_v, *rest, **kw):
        return write(
            k_cache, v_cache, _int8_rows(new_k), _int8_rows(new_v), *rest, **kw
        )

    return rounded


def arm(cfg, name: str):
    """``(config the program is built with, [(module, attribute, wrong
    value)])`` of an arm; the reference always gets the file's config."""
    updates = {'experts_16_31': {'first_local_expert': cfg.num_local_experts}}
    patches = {
        'no_bias': [(lfm2, 'routed_experts', _no_bias)],
        'no_qk_norm': [(lfm2, '_qkv', _no_qk_norm)],
        'state_fp8': [
            (lfm2, 'conv_span', _state_fp8_span),
            (lfm2, 'conv_step', _state_fp8_step),
        ],
        'state_late': [
            (lfm2, 'conv_span', _state_late_span),
            (lfm2, 'conv_step', _state_late_step),
        ],
        # The model's programs import these when they are traced.
        'int8_kv': [
            (paged_attention, name_,
             _int8_writer(getattr(paged_attention, name_)))
            for name_ in ('write_chunk_kv', 'write_token_kv')
        ],
    }
    return cfg.model_copy(update=updates.get(name, {})), patches.get(name, [])


def _workload(model: dict) -> dict:
    cell = next(ROOT.glob('benchmarks/workloads/lfm2-*.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    return workload


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    workload = _workload(model)
    as_served = lfm2_closed._model_cfg
    for seed in seeds:
        for name in arms:
            cfg, patches = arm(as_served(model), name)
            lfm2_closed._model_cfg = lambda m, cfg=cfg: cfg
            ctx = SimpleNamespace(
                config=model, seed=seed, workload=workload,
                traffic=workload['traffic'],
                rehearsal=jax.devices()[0].platform != 'tpu',
            )
            try:
                with patched(patches):
                    state = lfm2_closed.build(ctx)
                    seconds = lfm2_closed.sample_for_check(state, ctx)
            finally:
                lfm2_closed._model_cfg = as_served
            if name == 'one_row':
                prompts, outputs, held = state['check']
                rows = list(range(len(prompts)))
                rows[3] = 4  # row 3 holds what row 4 does
                state['check'] = (
                    prompts, outputs, tuple(pool[rows] for pool in held)
                )
            correct, detail = lfm2_closed.verify(state, ctx, {'failed': 0})
            for key in ('kv_pools', 'state_pool'):
                detail.pop(key)
            print(json.dumps({
                'seed': seed, 'arm': name,
                'device': jax.devices()[0].device_kind,
                'correct': correct, 'check_s': round(seconds, 1), **detail,
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
    config = ROOT / 'benchmarks/configs/lfm2-8b-a1b.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    seeds = [int(a) for a in args] or [3200000023]
    check(json.loads(config.read_text()), seeds, opts.arms.split(','))
    return 0


if __name__ == '__main__':
    sys.exit(main())
