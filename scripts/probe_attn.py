"""Isolate attention's share of the embed forward on the real chip.

Times the bf16 BERT-base forward at the bench's hot shape [512, 256] in
three variants: full SDPA, attention stubbed to identity (x = v), and — if
available — the custom Pallas encoder-attention kernel. The gap between
full and stubbed bounds what an attention kernel can buy (VERDICT r2
weak #4: device MFU 0.43 vs padded tokens)."""

from __future__ import annotations

import pathlib as _pl
import sys as _sys
_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.models import bert, common


def timed(fn, params, ids, mask, n=8):
    out = fn(params, ids, mask)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(n):
        out = fn(params, ids, mask)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / n


def main() -> None:
    B, S = 512, 256
    cfg = bert.BertConfig(dtype='bfloat16')
    params = jax.device_put(bert.init(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, S)), jnp.int32)
    mask = jnp.ones((B, S), jnp.int32)

    full = jax.jit(lambda p, i, m: bert.apply(p, cfg, i, m))
    t_full = timed(full, params, ids, mask)

    orig_sdpa = common.sdpa
    common.sdpa = lambda q, k, v, **kw: v  # stub
    try:
        stub = jax.jit(lambda p, i, m: bert.apply(p, cfg, i, m))
        t_stub = timed(stub, params, ids, mask)
    finally:
        common.sdpa = orig_sdpa

    tokens = B * S
    flops = 2 * 110e6 * tokens
    print(f'full forward:    {t_full*1e3:7.1f} ms  mfu={flops/t_full/197e12:.3f}')
    print(f'attention=ident: {t_stub*1e3:7.1f} ms  mfu={flops/t_stub/197e12:.3f}')
    print(f'attention cost:  {(t_full-t_stub)*1e3:7.1f} ms '
          f'({(t_full-t_stub)/t_full:.1%} of forward)')

    try:
        from distllm_tpu.ops.encoder_attention import encoder_attention  # noqa: F401

        common.sdpa = None  # ensure unused
        fast = jax.jit(
            lambda p, i, m: bert.apply(p, cfg, i, m, attn_impl='pallas')
        )
        t_fast = timed(fast, params, ids, mask)
        print(f'pallas kernel:   {t_fast*1e3:7.1f} ms  '
              f'mfu={flops/t_fast/197e12:.3f}')
    except Exception as exc:  # kernel not built yet / no attn_impl arg
        print('pallas variant skipped:', repr(exc)[:200])
    finally:
        common.sdpa = orig_sdpa


if __name__ == '__main__':
    main()
