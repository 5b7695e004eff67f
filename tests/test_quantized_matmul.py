"""int8 matmul tiers: pallas-interpret == xla == dequantize reference.

The serving claim under test: ``int8_dense`` computes the same thing as
``x @ QTensor.dequantize()`` while never materializing a float weight —
the property ``ops/quantized_matmul.py``'s docstring states.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from distllm_tpu.models import common
from distllm_tpu.ops import quantized_matmul as qmm
from distllm_tpu.ops.quantization import quantize_int8


def _case(m, k, n, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(
        rng.standard_normal((m, k)).astype(np.float32), dtype=dtype
    )
    w = rng.standard_normal((k, n)).astype(np.float32) * 0.05
    qt = quantize_int8(w, out_dtype='bfloat16')
    ref = np.asarray(
        jnp.asarray(x, jnp.float32) @ jnp.asarray(qt.dequantize(), jnp.float32)
    )
    return x, qt, ref


def _assert_close(out, ref):
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=0.05, atol=0.05
    )


def test_xla_tier_matches_dequantize():
    x, qt, ref = _case(8, 512, 256)
    _assert_close(qmm.int8_matmul_xla(x, qt.q, qt.scale), ref)


def test_pallas_interpret_matches_xla():
    x, qt, _ = _case(32, 512, 256)
    got = qmm.int8_matmul_pallas(x, qt.q, qt.scale, interpret=True)
    want = qmm.int8_matmul_xla(x, qt.q, qt.scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=0.02,
        atol=0.02,
    )


def test_pallas_row_padding():
    # M=5 pads to the 16-row sublane tile; padded rows must not leak.
    x, qt, ref = _case(5, 512, 256)
    got = qmm.int8_matmul_pallas(x, qt.q, qt.scale, interpret=True)
    assert got.shape == (5, 256)
    _assert_close(got, ref)


def test_int8_dense_leading_dims():
    x, qt, ref = _case(6, 512, 256)
    x3 = x.reshape(2, 3, 512)
    got = qmm.int8_dense(x3, qt.q, qt.scale, backend='xla')
    assert got.shape == (2, 3, 256)
    _assert_close(got.reshape(6, 256), ref)


def test_int8_dense_interpret_backend():
    x, qt, ref = _case(4, 512, 128)
    _assert_close(qmm.int8_dense(x, qt.q, qt.scale, backend='interpret'), ref)


@pytest.mark.parametrize(
    'm,k,n,ok',
    [
        (8, 512, 384, True),  # 384 = 3*128: a valid tile exists
        (8, 300, 256, False),  # K has no 128-multiple tile
        (8, 512, 200, False),  # N has no 128-multiple tile
        (qmm.MAX_PALLAS_ROWS + 1, 512, 256, False),  # prefill-sized M
    ],
)
def test_tile_contract(m, k, n, ok):
    assert qmm.pallas_supported(m, k, n) is ok


def test_unknown_backend_rejected():
    x, qt, _ = _case(4, 512, 128)
    with pytest.raises(ValueError, match='unknown quantized-matmul'):
        qmm.int8_dense(x, qt.q, qt.scale, backend='Pallas')


def test_common_dense_routes_int8():
    # dense() must dispatch 2-D int8 QTensors to int8_dense (no float
    # weight), honoring the process tier, and still apply bias.
    qmm.set_default_backend('interpret')
    try:
        x, qt, ref = _case(4, 512, 256)
        bias = jnp.asarray(np.linspace(-1, 1, 256), jnp.bfloat16)
        got = common.dense(x, qt, bias)
    finally:
        qmm.set_default_backend('auto')
    _assert_close(got, ref + np.asarray(bias, np.float32))


def test_set_default_backend_validates():
    with pytest.raises(ValueError):
        qmm.set_default_backend('cuda')
    assert qmm.default_backend() == 'auto'


def test_common_dense_nf4_still_dequantizes():
    from distllm_tpu.ops.quantization import quantize_nf4

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.bfloat16)
    w = rng.standard_normal((256, 128)).astype(np.float32) * 0.05
    qt = quantize_nf4(w, 64, 'bfloat16')
    ref = np.asarray(
        jnp.asarray(x, jnp.float32) @ jnp.asarray(qt.dequantize(), jnp.float32)
    )
    _assert_close(common.dense(x, qt), ref)
