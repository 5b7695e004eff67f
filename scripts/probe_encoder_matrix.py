"""Encoder-attention backend matrix: XLA SDPA vs Pallas kernel, by shape.

VERDICT r3 weak #6: at the bench's hot shape the two backends tie, so the
kernel must either show a shape regime where it wins (then `auto` routes
there) or default off. This sweeps the regimes the embed pipeline actually
serves — BERT-base across the fine bucket ladder, ESM2-650M protein
lengths, ModernBERT long buckets with the sliding-window bias — and prints
one JSON line per (family, S, backend) with ms/forward and tokens/s.

Token budget per forward is held ~constant (B*S ~= 128k) so lines compare
like-for-like. shape_supported gates the Pallas rows (whole-[S, N*Hd]
slices must fit VMEM; e.g. ESM2-650M tops out at S=512).
"""

from __future__ import annotations

import json
import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import bert, esm2, modernbert
from distllm_tpu.ops.encoder_attention import shape_supported

TOKEN_BUDGET = 1 << 17


def timed(fn, *args, n=6):
    out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    np.asarray(jax.tree.leaves(out)[0][0, 0])
    return (time.perf_counter() - start) / n


def emit(**kw):
    print(json.dumps(kw), flush=True)


def sweep(family: str, cfg, module, seqs):
    params = jax.device_put(module.init(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    for s in seqs:
        b = max(8, TOKEN_BUDGET // s)
        ids = jnp.asarray(
            rng.integers(4, cfg.vocab_size, size=(b, s)), jnp.int32
        )
        mask = jnp.ones((b, s), jnp.int32)
        backends = ['xla']
        if shape_supported(s, cfg.hidden_size, cfg.num_heads, 2,
                           has_bias='modernbert' in family):
            backends.append('pallas')
        for impl in backends:
            fn = jax.jit(
                lambda p, i, m, impl=impl: module.apply(
                    p, cfg, i, m, attn_impl=impl
                )
            )
            try:
                sec = timed(fn, params, ids, mask)
            except Exception as exc:  # Mosaic reject etc. — record, move on
                emit(family=family, seq=s, batch=b, backend=impl,
                     error=repr(exc)[:200])
                continue
            emit(
                family=family, seq=s, batch=b, backend=impl,
                ms=round(sec * 1e3, 1),
                tokens_per_s=round(b * s / sec),
                platform=jax.default_backend(),
            )
    del params


def main() -> None:
    bert_cfg = bert.BertConfig(dtype='bfloat16')
    sweep('bert-base', bert_cfg, bert, (160, 224, 256, 320, 352, 512))

    esm_cfg = esm2.Esm2Config(  # 650M dims (t33)
        vocab_size=33, hidden_size=1280, num_layers=33, num_heads=20,
        intermediate_size=5120, dtype='bfloat16',
    )
    sweep('esm2-650m', esm_cfg, esm2, (256, 512, 1024))

    mb_cfg = modernbert.ModernBertConfig(dtype='bfloat16')
    sweep('modernbert-base', mb_cfg, modernbert, (256, 512, 1024))


if __name__ == '__main__':
    main()
