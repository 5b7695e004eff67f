"""Second embed breakdown: per-bucket-shape MFU and numpy-vs-device input
cost. probe_attn showed the bare forward at [512, 256] hits 0.642 MFU with
attention ~free, while the pipeline measures 0.432 vs padded tokens — this
isolates whether the gap is (a) odd bucket shapes, (b) host->device input
transfer per dispatch, or (c) the fused pooling epilogue."""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.embed import get_pooler
from distllm_tpu.embed.encoders.base import JaxEncoder
from distllm_tpu.models import bert
from distllm_tpu.models.tokenizer import WhitespaceTokenizer


def main() -> None:
    cfg = bert.BertConfig(dtype='bfloat16')
    params = jax.device_put(bert.init(jax.random.PRNGKey(0), cfg))
    tokenizer = WhitespaceTokenizer(vocab_size=cfg.vocab_size,
                                    model_max_length=512)
    encoder = JaxEncoder(
        config=None, apply_fn=bert.apply, model_cfg=cfg, params=params,
        tokenizer=tokenizer, embedding_size=cfg.hidden_size,
    )
    pooler = get_pooler({'name': 'mean'})
    fused = encoder.pooled_forward(pooler, False)
    rng = np.random.default_rng(0)
    B = 512

    class Batch:
        def __init__(self, ids, mask):
            self.input_ids = ids
            self.attention_mask = mask

    for S in (160, 224, 256, 320):
        ids_np = rng.integers(1, cfg.vocab_size, size=(B, S)).astype(np.int32)
        mask_np = np.ones((B, S), np.int32)
        b_np = Batch(ids_np, mask_np)
        b_dev = Batch(jnp.asarray(ids_np), jnp.asarray(mask_np))
        jax.block_until_ready(fused(b_dev))  # warm

        for name, b in (('dev', b_dev), ('np ', b_np)):
            n = 6
            outs = [fused(b) for _ in range(2)]
            jax.block_until_ready(outs)
            start = time.perf_counter()
            outs = [fused(b) for _ in range(n)]
            jax.block_until_ready(outs)
            dt = (time.perf_counter() - start) / n
            mfu = 2 * 110e6 * B * S / dt / 197e12
            print(f'S={S} {name} inputs: {dt*1e3:6.1f} ms/batch  '
                  f'mfu(padded)={mfu:.3f}')


def switching() -> None:
    """Dispatch the four shapes round-robin: is executable switching the
    hidden cost that single-pass runs pay?"""
    cfg = bert.BertConfig(dtype='bfloat16')
    params = jax.device_put(bert.init(jax.random.PRNGKey(0), cfg))
    tokenizer = WhitespaceTokenizer(vocab_size=cfg.vocab_size,
                                    model_max_length=512)
    encoder = JaxEncoder(
        config=None, apply_fn=bert.apply, model_cfg=cfg, params=params,
        tokenizer=tokenizer, embedding_size=cfg.hidden_size,
    )
    pooler = get_pooler({'name': 'mean'})
    fused = encoder.pooled_forward(pooler, False)
    rng = np.random.default_rng(0)
    B = 512

    class Batch:
        def __init__(self, ids, mask):
            self.input_ids = ids
            self.attention_mask = mask

    shapes = (160, 224, 256, 320)
    batches = []
    for S in shapes:
        ids = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(B, S)), jnp.int32)
        batches.append(Batch(ids, jnp.ones((B, S), jnp.int32)))
        jax.block_until_ready(fused(batches[-1]))
    tokens = B * sum(shapes)
    for trial in range(3):
        start = time.perf_counter()
        outs = [fused(b) for b in batches]
        jax.block_until_ready(outs)
        dt = time.perf_counter() - start
        print(f'round-robin pass {trial}: {dt*1e3:6.1f} ms  '
              f'mfu={2*110e6*tokens/dt/197e12:.3f}')


if __name__ == '__main__':
    import sys
    if '--switching' in sys.argv:
        switching()
    else:
        main()
