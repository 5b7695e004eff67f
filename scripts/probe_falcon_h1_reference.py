#!/usr/bin/env python3
"""Calibrate ``reference_falcon_h1``'s limits on the chip (PR 41, as PR 39 did
for lfm2), at the benchmark configuration's widths against the float32
reference, with the wrong programs the limits have to catch.

    chiprun -- python scripts/probe_falcon_h1_reference.py [config.json] [--arms a,b] [seed ...]

Each arm is the cell's own check (``falcon_h1_closed.sample_for_check`` and
``verify``: the greedy calls at the cell's load through ``LLMEngine``, then
the reference) on an engine built as the arm says; one JSON line an arm.
Arms: ``program`` (as served); ``spreads`` (no engine: what the seeded
weights make of layer 0 and of the logits, for the configuration's
``assumed``); ``no_attention`` (``a = 0``); ``no_mamba`` (``m = 0``);
``no_key_multiplier``; ``no_ssm_multipliers`` (the five-part multiplier
dropped); ``group0`` (heads 16-31 given group 0's B and C); ``norm_all`` (the
gated norm taken over all channels at once); ``no_rope``; ``ssm_bf16`` (the
SSM state rounded to bfloat16 whenever it is written: the nearest precision
below the one the state pool states); ``int8_kv`` (every K and V row rounded
to int8, one scale a token and head, before it enters the pool: the nearest
precision below the one the K/V pool states); ``no_zeroing`` (a sequence's
first span starts from what its slot held).
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
import numpy as np

from benchmarks.drivers import falcon_h1_closed
from probe_deepseek_reference import patched
from probe_lfm2_reference import _int8_writer
from distllm_tpu.models import falcon_h1, granite_hybrid
from distllm_tpu.ops import paged_attention
from distllm_tpu.utils import enable_compile_cache

ARMS = (
    'program,spreads,no_attention,no_mamba,no_key_multiplier,'
    'no_ssm_multipliers,group0,norm_all,no_rope,ssm_bf16,int8_kv,no_zeroing'
)


def _no_attention(attn, lp, cfg, out=falcon_h1._attn_out):
    return jnp.zeros_like(out(attn, lp, cfg))


def _no_mixer(mixer):
    def dropped(*args, **kw):
        out, ssm, conv = mixer(*args, **kw)
        return jnp.zeros_like(out), ssm, conv

    return dropped


def _group0(lp, cfg, window, inputs=granite_hybrid._mamba_inputs):
    x, b_in, c_in = inputs(lp, cfg, window)
    first = lambda t: jnp.broadcast_to(t[..., :1, :], t.shape)  # noqa: E731
    return x, first(b_in), first(c_in)


def _norm_all(y, z, lp, cfg, dtype, out=granite_hybrid._mamba_out):
    return out(y, z, lp, cfg.model_copy(update={'mamba_n_groups': 1}), dtype)


def _no_rope(cfg, max_len, tables=falcon_h1._rope_tables):
    cos, sin = tables(cfg, max_len)
    return jnp.ones_like(cos), jnp.zeros_like(sin)


def _bf16_state(mixer):
    def rounded(*args, **kw):
        out, ssm, conv = mixer(*args, **kw)
        # a pair of converts is a round trip the compiler may drop
        return out, jax.lax.reduce_precision(ssm, 8, 7), conv

    return rounded


def _no_zeroing(state, slots, fresh, n, span_state=falcon_h1._span_state):
    return span_state(state, slots, jnp.zeros_like(fresh), n)


def arm(cfg, name: str):
    """``(config the program is built with, [(module, attribute, wrong
    value)])`` of an arm; the reference always gets the file's config."""
    updates = {
        'no_key_multiplier': {'key_multiplier': 1.0},
        'no_ssm_multipliers': {'ssm_multipliers': None},
    }
    mixers = ('mamba_span', 'mamba_step')
    patches = {
        'no_attention': [(falcon_h1, '_attn_out', _no_attention)],
        'no_mamba': [
            (falcon_h1, m, _no_mixer(getattr(falcon_h1, m))) for m in mixers
        ],
        'group0': [(granite_hybrid, '_mamba_inputs', _group0)],
        'norm_all': [(granite_hybrid, '_mamba_out', _norm_all)],
        'no_rope': [(falcon_h1, '_rope_tables', _no_rope)],
        'ssm_bf16': [
            (falcon_h1, m, _bf16_state(getattr(falcon_h1, m))) for m in mixers
        ],
        # The model's programs import these when they are traced.
        'int8_kv': [
            (paged_attention, name_,
             _int8_writer(getattr(paged_attention, name_)))
            for name_ in ('write_chunk_kv', 'write_token_kv')
        ],
        'no_zeroing': [(falcon_h1, '_span_state', _no_zeroing)],
    }
    return cfg.model_copy(update=updates.get(name, {})), patches.get(name, [])


def _workload(model: dict) -> dict:
    cell = next(ROOT.glob('benchmarks/workloads/falcon-h1-*.json'))
    workload = json.loads(cell.read_text())
    if 'check_traffic' in model:  # a toy size, to rehearse on the CPU
        workload['traffic'].update(model['check_traffic'])
    return workload


def spreads(model: dict, ctx) -> dict:
    """What the seeded weights make of one 512-token row: layer 0's
    attention scores (standard deviation over the causal entries), the
    decay ``exp(dt A)`` a step over heads and tokens, the RMS of the two
    mixers' outputs ``m`` and ``a`` and of the MLP's against the
    residual's, and the logits' standard deviation at the last position
    (``reference_falcon_h1``'s own programs)."""
    from benchmarks import reference_falcon_h1 as ref

    params = falcon_h1_closed._weights(ctx)
    rng = np.random.default_rng(ctx.seed)
    ids = rng.integers(0, model['vocab_size'], (1, 512))
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    rms = lambda a: float(np.sqrt((f32(a) ** 2).mean()))  # noqa: E731
    lp = {n: jax.tree.map(lambda a: a[0], params['layers'][n]) for n in ref._MIXER}
    x = f32(params['embed'][jnp.asarray(ids[0])]) * model['embedding_multiplier']
    h = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + model['rms_norm_eps'])
    d, heads, kv = (
        model['head_dim'], model['num_attention_heads'],
        model['num_key_value_heads'],
    )
    q = (h @ f32(lp['q']['kernel'])).reshape(-1, heads, d)
    k = (h @ f32(lp['k']['kernel'])).reshape(-1, kv, d) * model['key_multiplier']
    scores = np.einsum('qnd,knd->nqk', q, np.repeat(k, heads // kv, 1)) / np.sqrt(d)
    causal = np.tril(np.ones((len(h),) * 2, bool))
    di, gn = model['mamba_d_ssm'], model['mamba_n_groups'] * model['mamba_d_state']
    p = (h * model['ssm_in_multiplier']) @ f32(lp['in_proj']['kernel'])
    dt = p[:, di + di + 2 * gn:] * model['ssm_multipliers'][4] + f32(lp['dt_bias'])
    decay = np.exp(-np.log1p(np.exp(dt)) * np.exp(f32(lp['A_log'])))
    logits, _ = ref.forward(params, model, ids, [[511]])
    # the mixers' outputs, from a second forward's pieces: the reference's
    # own mix program on layer 0, with each half's multiplier zeroed
    mix = {}
    for half, key in (('m', 'attention_out_multiplier'), ('a', 'ssm_out_multiplier')):
        only = dict(model, **{key: 0.0})
        programs = ref._programs(ref._numbers(only))
        cos, sin = ref.rope_angles(model['rope_theta'], d, np.arange(512))
        out, _, _ = programs[0](
            jnp.asarray(x), ref._mixer_stacks(params['layers']), jnp.int32(0),
            cos, sin, jnp.int32(512),
        )
        mix[half] = rms(f32(out) - x)
    return {
        'scores_std': float(scores[:, causal].std()),
        'decay_min_median_max': [
            float(v) for v in np.quantile(decay, [0.0, 0.5, 1.0])
        ],
        'x_rms': rms(x), 'm_rms': mix['m'], 'a_rms': mix['a'],
        'logits_std': float(logits.std()),
    }


def check(model: dict, seeds: list[int], arms: list[str]) -> None:
    """The cell's check on an engine built as each arm says."""
    workload = _workload(model)
    as_served = falcon_h1_closed._model_cfg
    for seed in seeds:
        for name in arms:
            ctx = SimpleNamespace(
                config=model, seed=seed, workload=workload,
                traffic=workload['traffic'],
                rehearsal=jax.devices()[0].platform != 'tpu',
            )
            head = {
                'seed': seed, 'arm': name,
                'device': jax.devices()[0].device_kind,
            }
            if name == 'spreads':
                print(json.dumps({**head, **spreads(model, ctx)}), flush=True)
                continue
            cfg, patches = arm(as_served(model), name)
            falcon_h1_closed._model_cfg = lambda m, cfg=cfg: cfg
            try:
                with patched(patches):
                    state = falcon_h1_closed.build(ctx)
                    seconds = falcon_h1_closed.sample_for_check(state, ctx)
            finally:
                falcon_h1_closed._model_cfg = as_served
            correct, detail = falcon_h1_closed.verify(state, ctx, {'failed': 0})
            for key in (
                'kv_pools', 'state_pool', 'kernel_call_s', 'scope_s',
                'setup_split_s',
            ):
                detail.pop(key)
            print(json.dumps({
                **head, 'correct': correct, 'check_s': round(seconds, 1),
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
    config = ROOT / 'benchmarks/configs/falcon-h1-34b.json'
    if args and args[0].endswith('.json'):  # a toy size, to rehearse on the CPU
        config = Path(args.pop(0))
    seeds = [int(a) for a in args] or [3200000023]
    check(json.loads(config.read_text()), seeds, opts.arms.split(','))
    return 0


if __name__ == '__main__':
    sys.exit(main())
