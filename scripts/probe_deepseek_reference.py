#!/usr/bin/env python3
"""Calibrate ``reference_deepseek_v3``'s limits on the chip (PR 32, as PR 30
did for laguna), at the benchmark configuration's widths against the float32
reference, with the wrong programs the limits have to catch.

    chiprun -- python scripts/probe_deepseek_reference.py [config.json] [--arms a,b] [seed ...]

Each arm is the cell's own check (``deepseek_v3_closed.sample_for_check`` and
``verify``: the greedy call at the cell's load through ``LLMEngine``, then
the reference) on an engine built as the arm says; one JSON line an arm.
Arms: ``program`` (as served); ``scale_128`` (scores over ``sqrt(128)``, the
no-rope width, and not ``sqrt(192)``); ``no_rope_key`` (``q_r . k_r`` left out
of the scores); ``no_kv_norm`` (``kv_a_layernorm`` left out); ``value_lanes``
(values read from lanes 64-575 of the cached row and not 0-511);
``no_bias`` (the selection bias left out); ``softmax`` (softmax scoring of
the router); ``no_scale`` (the 2.448 left out); ``no_renorm`` (the kept
scores not renormalised); ``int8_rows`` (the nearest precision below the one
the configuration states: every latent row rounded to int8 before it enters
the pool, one scale a token, an int8 pool at its best; the engine refuses a
real one for a latent group).
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

from benchmarks.drivers import deepseek_v3_closed
from distllm_tpu.models import deepseek_v3
from distllm_tpu.ops import paged_attention
from distllm_tpu.utils import enable_compile_cache

ARMS = (
    'program,scale_128,no_rope_key,no_kv_norm,value_lanes,no_bias,softmax,'
    'no_scale,no_renorm,int8_rows'
)


def _no_rope_key(q_n, q_r, lp, cfg, absorb=deepseek_v3._absorb_queries):
    return absorb(q_n, jnp.zeros_like(q_r), lp, cfg)


def _no_kv_norm(normed, lp, cfg, cos, sin, positions,
                parts=deepseek_v3._latent_parts):
    lp = {**lp, 'kv_ln': {'scale': None}}
    saved = deepseek_v3._norm
    deepseek_v3._norm = lambda x, scale, cfg: x if scale is None else saved(
        x, scale, cfg
    )
    try:
        return parts(normed, lp, cfg, cos, sin, positions)
    finally:
        deepseek_v3._norm = saved


def _shifted_values(attend, shift=64):
    """An attention entry point whose values are lanes ``shift`` onward of
    the cached rows: rows and queries rolled alike (the scores are what
    they were), so the leading ``value_lanes`` are the wrong ones."""

    def wrong(q, plane, *rest, **kw):
        return attend(
            jnp.roll(q, -shift, axis=-1), jnp.roll(plane, -shift, axis=-1),
            *rest, **kw,
        )

    return wrong


def _router(scoring=None, bias=True, renorm=True,
            routed=deepseek_v3.routed_experts):
    def wrong(x, router_kernel, *banks, select_bias=None, **kw):
        if scoring is not None:
            kw['scoring'], select_bias = scoring, None
        out, pairs = routed(
            x, router_kernel, *banks,
            select_bias=select_bias if bias else None, **kw,
        )
        if not renorm:  # w_e = scale * s_e: every gate times the kept sum
            scores = jax.nn.sigmoid(jnp.einsum(
                'th,he->te', x.astype(jnp.float32),
                router_kernel.astype(jnp.float32),
            ))
            _, top = jax.lax.top_k(scores + select_bias, banks[3])
            kept = jnp.take_along_axis(scores, top, axis=-1).sum(-1)
            out = (out.astype(jnp.float32) * kept[:, None]).astype(out.dtype)
        return out, pairs

    return wrong


def _int8_rows(rows):
    """``rows [..., 1, row]`` as an int8 pool would hand them back: 255
    levels, one scale a token."""
    scale = (
        jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)
        / paged_attention.KV_QUANT_MAX
    )
    q = paged_attention.quantize_kv_rows(rows, scale)  # the int8 pool's own
    return (q.astype(jnp.float32) * scale[..., None]).astype(rows.dtype)


def _int8_writer(write):
    def rounded(plane, none, rows, *rest):
        return write(plane, none, _int8_rows(rows), *rest)

    return rounded


def arm(cfg, name: str):
    """``(config the program is built with, [(module, attribute, wrong
    value)])`` of an arm; the reference always gets the file's config."""
    updates = {'no_scale': {'routed_scaling_factor': 1.0}}
    # The leaves the programs' attention ends in, each met once on a path.
    attend = ('ragged_paged_attention_pallas', 'ragged_paged_attention_xla',
              'paged_attention_xla')
    patches = {
        'scale_128': [(
            deepseek_v3.DeepseekV3Config, 'softmax_scale',
            property(lambda self: self.qk_nope_head_dim ** -0.5),
        )],
        'no_rope_key': [(deepseek_v3, '_absorb_queries', _no_rope_key)],
        'no_kv_norm': [(deepseek_v3, '_latent_parts', _no_kv_norm)],
        # The model's programs import these when they are traced.
        'value_lanes': [
            (paged_attention, name_,
             _shifted_values(getattr(paged_attention, name_)))
            for name_ in attend
        ],
        'no_bias': [(deepseek_v3, 'routed_experts', _router(bias=False))],
        'softmax': [(deepseek_v3, 'routed_experts', _router('softmax'))],
        'no_renorm': [(deepseek_v3, 'routed_experts', _router(renorm=False))],
        'int8_rows': [
            (paged_attention, name_,
             _int8_writer(getattr(paged_attention, name_)))
            for name_ in ('write_chunk_kv', 'write_token_kv')
        ],
    }
    return cfg.model_copy(update=updates.get(name, {})), patches.get(name, [])


class patched:
    """``with patched(patches):`` the wrong values in place, the right ones
    back afterwards."""

    def __init__(self, patches) -> None:
        self.patches = patches

    def __enter__(self):
        self.saved = [
            (mod, name, mod.__dict__[name]) for mod, name, _ in self.patches
        ]
        for mod, name, wrong in self.patches:
            setattr(mod, name, wrong)

    def __exit__(self, *exc) -> None:
        for mod, name, right in self.saved:
            setattr(mod, name, right)


def _workload(model: dict) -> dict:
    cell = next(ROOT.glob('benchmarks/workloads/kanana-*.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    return workload


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    workload = _workload(model)
    as_served = deepseek_v3_closed._model_cfg
    for seed in seeds:
        for name in arms:
            cfg, patches = arm(as_served(model), name)
            deepseek_v3_closed._model_cfg = lambda m, cfg=cfg: cfg
            ctx = SimpleNamespace(
                config=model, seed=seed, workload=workload,
                traffic=workload['traffic'],
                rehearsal=jax.devices()[0].platform != 'tpu',
            )
            try:
                with patched(patches):
                    state = deepseek_v3_closed.build(ctx)
                    seconds = deepseek_v3_closed.sample_for_check(state, ctx)
            finally:
                deepseek_v3_closed._model_cfg = as_served
            correct, detail = deepseek_v3_closed.verify(
                state, ctx, {'failed': 0}
            )
            detail.pop('kv_pools')
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
    config = ROOT / 'benchmarks/configs/kanana-2-30b-a3b.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    seeds = [int(a) for a in args] or [3200000023]
    check(json.loads(config.read_text()), seeds, opts.arms.split(','))
    return 0


if __name__ == '__main__':
    sys.exit(main())
