"""Does the prefill executable tolerate the decode-window's preferred
weight layouts without inserting layout-conversion copies?"""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from distllm_tpu.models import mistral

cfg = mistral.MistralConfig(dtype='bfloat16')
L, bs, kv, hd = cfg.num_layers, 16, cfg.num_kv_heads, cfg.head_size
b, num_blocks, R = 32, 712, 32
params_sh = jax.eval_shape(
    lambda: mistral.init_on_device(jax.random.PRNGKey(0), cfg)
)
S = jax.ShapeDtypeStruct
shapes = [
    params_sh, S((b,), jnp.int32), S((b,), jnp.int32), S((b,), jnp.int32),
    S((L, num_blocks, bs, kv, hd), jnp.bfloat16),
    S((L, num_blocks, bs, kv, hd), jnp.bfloat16),
    S((b, R), jnp.int32), S((b,), jnp.int32),
    S((b,), jnp.float32), S((b,), jnp.float32), S((b,), jnp.float32),
    S((b,), jnp.int32), S((b,), jnp.uint32),
]


def window(params, ids, pos, ctx, k, v, bt, steps, t, tp, mp, tk, sd):
    return mistral.decode_loop(
        params, cfg, ids, pos, k, v, bt, ctx, steps, t, tp, mp, tk, sd,
        num_steps=16, attn_backend='xla', max_table_positions=512,
    )


in_sh = (Format(Layout.AUTO),) + (Format(),) * 12
compiled = jax.jit(window, donate_argnums=(4, 5), in_shardings=in_sh).lower(
    *shapes
).compile()
fmts = compiled.input_formats[0][0]
ma = compiled.memory_analysis()
print(f'decode window: temp {ma.temp_size_in_bytes/2**30:.2f}G')


def prefill_fn(params, ids, mask, last_pos):
    hidden, k, v = mistral.prefill(params, cfg, ids, mask)
    last_hidden = jnp.take_along_axis(hidden, last_pos[:, None, None], axis=1)
    return mistral.logits(params, cfg, last_hidden)[:, 0], k, v


for pb, bucket in ((4, 512), (8, 256)):
    pshapes = [
        params_sh, S((pb, bucket), jnp.int32), S((pb, bucket), jnp.int32),
        S((pb,), jnp.int32),
    ]
    c_default = jax.jit(prefill_fn).lower(*pshapes).compile()
    c_decode_fmt = jax.jit(prefill_fn, in_shardings=(fmts, Format(), Format(), Format())).lower(*pshapes).compile()
    ma_d = c_default.memory_analysis()
    ma_f = c_decode_fmt.memory_analysis()
    print(f'prefill b={pb} S={bucket}: default-layout temp '
          f'{ma_d.temp_size_in_bytes/2**30:.2f}G | decode-layout temp '
          f'{ma_f.temp_size_in_bytes/2**30:.2f}G')
