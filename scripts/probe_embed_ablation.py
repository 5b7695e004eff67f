"""Embed-forward ablation: where do the non-matmul cycles go?

The AOT census of the bench's fused embed graph (B=512, S=256 bf16
BERT-base) shows the exact-erf GELU lowering as fp32 elementwise chains
over the [B, S, 3072] intermediate and fp32 LayerNorm stats — VPU work
and conversion traffic that may explain the 0.58-0.63 steady-state MFU
plateau of a 2026-07-31 record on older code (in git history; not
re-measured). This measures the forward with each
suspect ablated, on the real chip:

- full         : production graph
- act=identity : MLP activation removed (upper bound on GELU cost)
- act=tanh-gelu: approximate GELU (bf16-friendly polynomial, no erf)
- ln=bf16      : LayerNorm stats in bf16 instead of fp32

Numerics changes here are DIAGNOSTIC ONLY — production keeps HF-parity
numerics unless a measured win justifies a documented knob.
"""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import bert, common


def timed(fn, *args, n=8):
    out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    np.asarray(out[0, 0])
    return (time.perf_counter() - start) / n


def main() -> None:
    B, S = 512, 256
    cfg = bert.BertConfig(dtype='bfloat16')
    params = jax.device_put(bert.init(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, S)), jnp.int32)
    mask = jnp.ones((B, S), jnp.int32)
    n_params = sum(
        int(np.prod(np.shape(x))) for x in jax.tree.leaves(params)
    )
    flops = 2 * n_params * B * S

    def run(label, **patches):
        saved = {}
        try:
            for name, value in patches.items():
                saved[name] = getattr(common, name)
                setattr(common, name, value)
            if patches:  # activation table caches the function objects
                common.ACTIVATIONS['gelu'] = common.gelu
            fn = jax.jit(lambda p, i, m: bert.apply(p, cfg, i, m))
            sec = timed(fn, params, ids, mask)
        finally:
            for name, value in saved.items():
                setattr(common, name, value)
            common.ACTIVATIONS['gelu'] = common.gelu
        from bench import _chip_peak_flops

        peak = _chip_peak_flops(jax.devices()[0])
        mfu = round(flops / sec / peak, 3) if peak else None
        print(json.dumps({
            'variant': label, 'ms': round(sec * 1e3, 1),
            'mfu': mfu, 'platform': jax.default_backend(),
        }), flush=True)

    run('full')
    run('act_identity', gelu=lambda x: x)
    run('act_tanh_gelu', gelu=lambda x: jax.nn.gelu(x, approximate=True))

    orig_ln = common.layer_norm

    def ln_bf16(x, scale, bias, eps):
        return orig_ln(x.astype(jnp.bfloat16), scale, bias, eps)

    run('ln_bf16', layer_norm=ln_bf16)


if __name__ == '__main__':
    main()
