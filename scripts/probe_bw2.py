"""Device-rate probe: in-jit repetition, slope timing.

Timing single dispatches from the host measures dispatch and sync, not
the device. Here every measured op runs R times INSIDE one jitted
``lax.fori_loop`` with a data dependency that defeats CSE/hoisting, and
the device time per op is the slope between two R values — dispatch and
sync costs cancel.

What it measures (the calibration numbers every roofline claim rests on):

- HBM stream-read bandwidth (512 MiB sum per iteration),
- decode-regime matmul weight-stream rate at M=32/128 (bf16 and
  int8-weight scale-after-dot),
- the serving sampler (top_k(64)+full-vocab logsumexp over [B, 32k]) —
  per-step cost inside the decode window,
- paged KV scatter+gather at serving dims.
"""

from __future__ import annotations

import pathlib as _pl
import sys as _sys

_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import functools
import time

import jax
import jax.numpy as jnp
import numpy as np


def slope(make_fn, r1=4, r2=20):
    """Seconds per iteration from the (r2, r1) slope; RTT cancels."""
    f1, f2 = make_fn(r1), make_fn(r2)
    out = f1()
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    out = f2()
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]

    def timed(f, n=3):
        t0 = time.perf_counter()
        for _ in range(n):
            o = f()
        np.asarray(jax.tree.leaves(o)[0]).ravel()[:1]
        return (time.perf_counter() - t0) / n

    return max(1e-9, (timed(f2) - timed(f1)) / (r2 - r1))


def main() -> None:
    dev = jax.devices()[0]
    print(f'device: {dev.device_kind}')

    # --- HBM stream read ------------------------------------------------
    big = jnp.ones((512 * 1024 * 1024 // 4,), jnp.float32)

    def make_sum(r):
        @jax.jit
        def f(x):
            def body(_, acc):
                return jnp.sum(x + acc * 1e-30)

            return jax.lax.fori_loop(0, r, body, 0.0)

        return functools.partial(f, big)

    per = slope(make_sum)
    print(f'stream read 512 MiB: {per * 1e3:7.2f} ms/iter -> '
          f'{big.nbytes / per / 1e9:6.0f} GB/s')

    # --- decode matmul weight stream ------------------------------------
    for m in (32, 128):
        for name, wdtype in (('bf16', jnp.bfloat16), ('int8', jnp.int8)):
            k = n = 8192
            w = (jnp.ones((k, n), wdtype))
            s = jnp.ones((1, n), jnp.float32)
            x0 = jnp.ones((m, k), jnp.bfloat16)

            def make_mm(r, w=w, s=s, x0=x0, int8=(wdtype == jnp.int8)):
                @jax.jit
                def f(x, w, s):
                    def body(_, xc):
                        y = jax.lax.dot_general(
                            xc, w.astype(jnp.bfloat16) if int8 else w,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                        if int8:
                            y = y * s
                        return (xc + y.astype(jnp.bfloat16) * 1e-30)

                    return jax.lax.fori_loop(0, r, body, x)

                return functools.partial(f, x0, w, s)

            per = slope(make_mm)
            print(f'[{m:3d}x{k}x{n}] {name} matmul: {per * 1e6:8.1f} us/iter'
                  f' -> weight stream {w.nbytes / per / 1e9:6.0f} GB/s')

    # --- serving sampler -------------------------------------------------
    from distllm_tpu.ops.sampling import sample_tokens

    for b, v in ((32, 32000), (128, 32000)):
        logits0 = jnp.asarray(
            np.random.default_rng(0).standard_normal((b, v)), jnp.float32
        )
        temp = jnp.full((b,), 0.5, jnp.float32)
        top_p = jnp.full((b,), 0.95, jnp.float32)
        min_p = jnp.full((b,), 0.1, jnp.float32)

        def make_samp(r, logits0=logits0, temp=temp, top_p=top_p,
                      min_p=min_p):
            @jax.jit
            def f(lg, key):
                def body(i, carry):
                    lg_c, key_c = carry
                    key_c, sub = jax.random.split(key_c)
                    tok = sample_tokens(
                        lg_c, sub, temp, top_p, min_p, top_window=64
                    )
                    lg_c = lg_c + tok[:, None].astype(jnp.float32) * 1e-30
                    return (lg_c, key_c)

                return jax.lax.fori_loop(
                    0, r, body, (lg, key)
                )[0]

            return functools.partial(f, logits0, jax.random.PRNGKey(0))

        per = slope(make_samp)
        print(f'sampler tw=64 [B={b:3d}, V={v}]: {per * 1e6:8.1f} us/step'
              f' ({per * 16 * 1e3:5.1f} ms per 16-step window)')

    # --- lm_head + sampler combo (the per-step tail after the layers) ---
    for b in (32, 128):
        h0 = jnp.ones((b, 4096), jnp.bfloat16)
        wlm = jnp.ones((4096, 32000), jnp.bfloat16)
        temp = jnp.full((b,), 0.5, jnp.float32)
        top_p = jnp.full((b,), 0.95, jnp.float32)
        min_p = jnp.full((b,), 0.1, jnp.float32)

        def make_tail(r, h0=h0, wlm=wlm, temp=temp, top_p=top_p,
                      min_p=min_p):
            @jax.jit
            def f(h, w, key):
                def body(i, carry):
                    hc, key_c = carry
                    key_c, sub = jax.random.split(key_c)
                    lg = jax.lax.dot_general(
                        hc, w, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    tok = sample_tokens(
                        lg, sub, temp, top_p, min_p, top_window=64
                    )
                    hc = hc + tok[:, None].astype(jnp.bfloat16) * 1e-30
                    return (hc, key_c)

                return jax.lax.fori_loop(0, r, body, (h, key))[0]

            return functools.partial(f, h0, wlm, jax.random.PRNGKey(0))

        per = slope(make_tail)
        print(f'lm_head+sampler [B={b:3d}]: {per * 1e6:8.1f} us/step'
              f' ({per * 16 * 1e3:5.1f} ms per 16-step window)')


if __name__ == '__main__':
    main()
