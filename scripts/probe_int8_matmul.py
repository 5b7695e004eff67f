"""A/B the int8 matmul tiers against bf16 at the 7B decode shapes.

Run on the chip after the r5 run-1 finding (int8 decode windows 6x off
their floor): for each decode matmul shape of Mistral-7B this times

- bf16 dense (the bandwidth baseline: weight bytes = 2/elem),
- the OLD dequantize-then-dot formulation (what run 1 served),
- the XLA scale-after-dot tier,
- the Pallas in-VMEM-dequant kernel (weight bytes = 1/elem -> should beat
  bf16 by ~2x when weight-streaming bound).

Each case reports ms/call and achieved weight-stream GB/s. Small mode
(DISTLLM_BENCH_SMALL=1) runs tiny shapes on CPU (interpret for pallas)
to keep the probe itself tested.
"""

from __future__ import annotations

import os
import pathlib as _pl
import sys as _sys

_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import time

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.ops import quantized_matmul as qmm
from distllm_tpu.ops.quantization import quantize_int8


def _time(fn, *args, reps=64):
    """ms/call with the host sync amortized: queue `reps` async
    dispatches, host-sync ONCE on the last output. A sync per call would
    add the host's dispatch-and-fetch time to every reading."""
    out = fn(*args)
    np.asarray(out[0, :1])  # compile + settle
    t0 = time.perf_counter()
    for _ in range(reps - 1):
        out = fn(*args)
    out = fn(*args)
    np.asarray(out[0, :1])
    return (time.perf_counter() - t0) / reps


def main() -> None:
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    interpret = jax.default_backend() != 'tpu'
    if small:
        shapes = [(8, 512, 256)]
    else:
        # Mistral-7B decode matmuls at serving batches 32 and 128:
        # qkv [4096->6144 fused q+k+v], o [4096->4096],
        # gate/up [4096->14336], down [14336->4096], lm_head [4096->32000].
        shapes = [
            (b, k, n)
            for b in (32, 128)
            for k, n in [
                (4096, 4096),
                (4096, 14336),
                (14336, 4096),
                (4096, 32000),
            ]
        ]

    rng = np.random.default_rng(0)
    for m, k, n in shapes:
        x = jnp.asarray(
            rng.standard_normal((m, k)).astype(np.float32), jnp.bfloat16
        )
        w = rng.standard_normal((k, n)).astype(np.float32) * 0.02
        qt = quantize_int8(w)
        wd = jnp.asarray(w, jnp.bfloat16)
        del w

        bf16 = jax.jit(lambda a, b: a @ b)
        old = jax.jit(
            lambda a, q, s: a @ (q.astype(a.dtype) * s.astype(a.dtype))
        )
        xla = jax.jit(qmm.int8_matmul_xla)
        cases = [
            ('bf16', lambda: _time(bf16, x, wd), 2),
            ('old-dequant', lambda: _time(old, x, qt.q, qt.scale), 1),
            ('xla-scale-after', lambda: _time(xla, x, qt.q, qt.scale), 1),
        ]
        if qmm.pallas_supported(m, k, n):
            pallas = jax.jit(
                lambda a, q, s: qmm.int8_matmul_pallas(
                    a, q, s, interpret=interpret
                )
            )
            cases.append(
                ('pallas', lambda: _time(pallas, x, qt.q, qt.scale), 1)
            )
        print(f'[{m:4d}x{k:5d}x{n:5d}]', flush=True)
        for name, run, bytes_per_w in cases:
            try:
                sec = run()
                gbs = k * n * bytes_per_w / sec / 1e9
                print(
                    f'  {name:16s} {sec * 1e6:9.1f} us'
                    f'  weight-stream {gbs:7.1f} GB/s',
                    flush=True,
                )
            except Exception as exc:
                print(f'  {name:16s} FAILED {repr(exc)[:160]}', flush=True)


if __name__ == '__main__':
    main()
