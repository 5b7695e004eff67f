"""Embed-stage breakdown on the real chip: host tokenize vs device compute
vs end-to-end, plus padding-waste accounting — decides where the remaining
throughput gap lives (VERDICT r2 weak #4)."""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import time

import jax
import numpy as np

from distllm_tpu.embed import get_pooler
from distllm_tpu.embed.embedders.full_sequence import compute_embeddings
from distllm_tpu.embed.encoders.base import JaxEncoder
from distllm_tpu.models import bert
from distllm_tpu.models.tokenizer import WhitespaceTokenizer


def main() -> None:
    rng = np.random.default_rng(0)
    cfg = bert.BertConfig(
        vocab_size=30522, hidden_size=768, num_layers=12, num_heads=12,
        intermediate_size=3072, max_position_embeddings=512, dtype='bfloat16',
    )
    params = bert.init(jax.random.PRNGKey(0), cfg)
    tokenizer = WhitespaceTokenizer(vocab_size=cfg.vocab_size, model_max_length=512)
    encoder = JaxEncoder(
        config=None, apply_fn=bert.apply, model_cfg=cfg,
        params=jax.device_put(params), tokenizer=tokenizer,
        embedding_size=cfg.hidden_size,
    )
    pooler = get_pooler({'name': 'mean'})
    batch_size = 512

    vocab = [f'tok{i}' for i in range(5000)]
    texts = []
    for _ in range(2048):
        n = int(rng.integers(120, 260))
        texts.append(' '.join(rng.choice(vocab, size=n)))

    # Warm.
    compute_embeddings(texts, encoder, pooler, batch_size)

    # 1. End-to-end.
    start = time.perf_counter()
    compute_embeddings(texts, encoder, pooler, batch_size)
    e2e = time.perf_counter() - start
    print(f'end-to-end: {e2e*1e3:.0f} ms  ({2048/e2e:.0f} emb/s)')

    # 2. Host tokenize only (sorted order, same batching).
    order = sorted(range(len(texts)), key=lambda i: len(texts[i].split()))
    start = time.perf_counter()
    batches = []
    for lo in range(0, len(texts), batch_size):
        idx = order[lo:lo + batch_size]
        b = encoder.tokenizer([texts[i] for i in idx])
        batches.append((idx, b.pad_batch_to(batch_size, pad_id=0)))
    tok = time.perf_counter() - start
    total_padded = sum(b.input_ids.size for _, b in batches)
    total_real = sum(int(b.attention_mask.sum()) for _, b in batches)
    print(f'tokenize only: {tok*1e3:.0f} ms; padded tokens {total_padded} '
          f'real {total_real} (waste {1 - total_real/total_padded:.1%})')
    for _, b in batches:
        print('  batch shape', b.input_ids.shape)

    # 3. Device only (pre-tokenized batches, async dispatch, one final sync).
    fused = encoder.pooled_forward(pooler, False)
    outs = [fused(b) for _, b in batches]  # warm every shape
    np.asarray(outs[-1])
    start = time.perf_counter()
    outs = [fused(b) for _, b in batches]
    for o in outs:
        np.asarray(o)
    dev = time.perf_counter() - start
    print(f'device only: {dev*1e3:.0f} ms  ({2048/dev:.0f} emb/s)')
    flops = 2 * 110e6 * total_real
    print(f'device MFU vs real tokens: {flops/dev/197e12:.3f} '
          f'(vs padded: {2*110e6*total_padded/dev/197e12:.3f})')


if __name__ == '__main__':
    main()
