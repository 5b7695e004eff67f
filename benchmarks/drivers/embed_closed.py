"""Closed loop over the embedding pipeline: one corpus, embedded pass after
pass through ``compute_embeddings`` until the window is over. The window ends
at the end of the pass in flight.

Traffic parameters (``traffic`` of the cell's file): ``corpus`` (see
``traffic.corpus``) and ``batch_size``.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import reference, traffic

COSINE_LIMIT = 0.99  # chip_smoke.py's limit for bf16 against float32
CHECK_ROWS = 8


def prepare(ctx) -> dict:
    from distllm_tpu.embed import get_pooler
    from distllm_tpu.embed.encoders.base import JaxEncoder
    from distllm_tpu.models import bert
    from distllm_tpu.models.tokenizer import WhitespaceTokenizer

    model = ctx.config
    cfg = bert.BertConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )
    host_params = bert.init(jax.random.PRNGKey(ctx.seed % (2**31)), cfg)
    encoder = JaxEncoder(
        config=None,
        apply_fn=bert.apply,
        model_cfg=cfg,
        params=jax.device_put(host_params, ctx.devices[0]),
        tokenizer=WhitespaceTokenizer(
            vocab_size=cfg.vocab_size,
            model_max_length=cfg.max_position_embeddings,
        ),
        embedding_size=cfg.hidden_size,
    )
    state = {
        'encoder': encoder,
        'pooler': get_pooler({'name': model['pooling']}),
        'normalize': bool(model['normalize']),
        'host_params': host_params,
        'texts': traffic.corpus(ctx.traffic['corpus'], ctx.seed),
        'batch_size': int(ctx.traffic['batch_size']),
    }
    # Warm-up: one pass over the cell's own corpus compiles exactly the
    # bucket shapes the window will use.
    state['warm_rows'] = _one_pass(state, None)
    return state


def _one_pass(state, stats):
    from distllm_tpu.embed.embedders.full_sequence import compute_embeddings

    return compute_embeddings(
        state['texts'], state['encoder'], state['pooler'],
        state['batch_size'], normalize=state['normalize'], stats=stats,
    )


def measure(state, ctx) -> dict:
    stats: dict = {}
    passes = []
    rows = None
    ctx.capture.arm()
    t0 = time.perf_counter()
    while True:
        t_pass = time.perf_counter()
        with jax.profiler.TraceAnnotation('bench:pass'):
            rows = _one_pass(state, stats)
        now = time.perf_counter()
        passes.append((t_pass - t0, now - t0))
        over = now - t0 >= ctx.seconds
        ctx.capture.poll(last=over)
        if over:
            break
    window_s = time.perf_counter() - t0
    n_texts = len(state['texts'])
    state['last_rows'] = rows
    lengths = [
        min(len(t.split()) + 2, ctx.config['max_position_embeddings'])
        for t in state['texts']
    ]
    return {
        'end_to_end': {'emb_per_s': len(passes) * n_texts / window_s},
        'attempted': len(passes) * n_texts,
        'failed': 0,
        'window_s': window_s,
        'counters': {
            'passes': passes,
            'tokens_real': stats['tokens_real'],
            'tokens_padded': stats['tokens_padded'],
            'bucket_batches': stats['bucket_batches'],
            # Of one pass, for the FLOPs the real tokens need.
            'pass_tokens': sum(lengths),
            'pass_sum_sq_len': sum(n * n for n in lengths),
            'pass_texts': n_texts,
        },
        'flight': [],
    }


def verify(state, ctx, obs) -> tuple[bool, dict]:
    rows = state['last_rows']
    width = ctx.config['hidden_size']
    shape_ok = rows.shape == (len(state['texts']), width)
    finite = bool(np.isfinite(rows).all()) and bool(
        np.isfinite(state['warm_rows']).all()
    )
    picks = [
        int(i) for i in traffic.rng_for(ctx.seed, 'check').choice(
            len(state['texts']), size=min(CHECK_ROWS, len(state['texts'])),
            replace=False,
        )
    ]
    batch = state['encoder'].tokenizer([state['texts'][i] for i in picks])
    ref = reference.bert_embed(
        state['host_params'], ctx.config, batch.input_ids, batch.attention_mask
    )
    cos = reference.cosines(ref, rows[picks])
    failed_rows = 0 if finite else int((~np.isfinite(rows).all(axis=1)).sum())
    obs['failed'] = failed_rows * len(obs['counters']['passes'])
    correct = shape_ok and finite and min(cos) >= COSINE_LIMIT
    return correct, {'cosine_min': min(cos), 'rows_finite': finite}


def close(state) -> None:
    state['encoder'].shutdown()
