"""Bisect the decode-window program's HBM footprint via AOT memory analysis."""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import argparse

import jax
import jax.numpy as jnp

from distllm_tpu.models import mistral


def analyze(num_steps, attn_backend, num_blocks=488, b=24, sample=True):
    cfg = mistral.MistralConfig(dtype='bfloat16')
    L, bs, kv, hd = cfg.num_layers, 16, cfg.num_kv_heads, cfg.head_size
    R = (512 + bs - 1) // bs
    shapes = dict(
        params=jax.eval_shape(lambda: mistral.init_on_device(jax.random.PRNGKey(0), cfg)),
        ids=jax.ShapeDtypeStruct((b,), jnp.int32),
        pos=jax.ShapeDtypeStruct((b,), jnp.int32),
        ctx=jax.ShapeDtypeStruct((b,), jnp.int32),
        k=jax.ShapeDtypeStruct((L, num_blocks, bs, kv, hd), jnp.bfloat16),
        v=jax.ShapeDtypeStruct((L, num_blocks, bs, kv, hd), jnp.bfloat16),
        bt=jax.ShapeDtypeStruct((b, R), jnp.int32),
        steps=jax.ShapeDtypeStruct((b,), jnp.int32),
        f=jax.ShapeDtypeStruct((b,), jnp.float32),
        tk=jax.ShapeDtypeStruct((b,), jnp.int32),
        sd=jax.ShapeDtypeStruct((b,), jnp.uint32),
    )

    def fn(params, ids, pos, ctx, k, v, bt, steps, t, tp, mp, tk, sd):
        return mistral.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps, t, tp, mp, tk, sd,
            num_steps=num_steps, attn_backend=attn_backend,
            max_table_positions=512,
        )

    lowered = jax.jit(fn, donate_argnums=(4, 5)).lower(
        shapes['params'], shapes['ids'], shapes['pos'], shapes['ctx'],
        shapes['k'], shapes['v'], shapes['bt'], shapes['steps'],
        shapes['f'], shapes['f'], shapes['f'], shapes['tk'], shapes['sd'],
    )
    compiled = lowered.compile()
    try:
        ma = compiled.memory_analysis()
        print(f'steps={num_steps} backend={attn_backend}: '
              f'args {ma.argument_size_in_bytes/2**30:.2f}G '
              f'out {ma.output_size_in_bytes/2**30:.2f}G '
              f'temp {ma.temp_size_in_bytes/2**30:.2f}G '
              f'alias {ma.alias_size_in_bytes/2**30:.2f}G')
    except Exception as e:
        print('no memory_analysis:', e)


if __name__ == '__main__':
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=8)
    p.add_argument('--backend', default='xla')
    p.add_argument('--b', type=int, default=24)
    p.add_argument('--num-blocks', type=int, default=488)
    args = p.parse_args()
    analyze(args.steps, args.backend, num_blocks=args.num_blocks, b=args.b)
