"""The span form of the Kimi-delta rule as a Pallas kernel
(``ops/kda.py`` ``span_kernel``), on the Pallas interpreter at the published
head size (128) with few rows, heads and positions, against the
token-by-token recurrence (``kda_step`` in a loop); ``span_form``, the rule
that says where the kernel runs; and the engine's account of it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distllm_tpu.ops import kda
from solar_open2_toy import make_engine

D = 128
O_BOUND, STATE_BOUND = 2e-5, 1e-5  # tests/test_solar_open2.py's


def _inputs(seed=0, b=2, s=64, h=1, counted=(None, 20)):
    """``test_solar_open2._recurrence_inputs`` at the published head size:
    unit keys, the strongest decay in head 0 and beta at 1.99 in the last
    head where there are two, a state to start from, a second row that
    counts 20 positions. Most tests share one shape (two rows of one chunk,
    one head), so the interpreted kernel is built once for them."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, s, h, D)).astype(np.float32) * D ** -0.5
    k = rng.normal(size=(b, s, h, D)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, s, h, D)).astype(np.float32)
    g = -np.exp(
        rng.uniform(np.log(1e-3), np.log(1.6), size=(b, s, h, D))
    ).astype(np.float32)
    if h > 1:
        g[:, :, 0] = -1.6
    beta = rng.uniform(0, 2, size=(b, s, h)).astype(np.float32)
    if h > 1:
        beta[:, :, -1] = 1.99
    tails = np.asarray([s if n is None else n for n in counted[:b]])
    valid = np.arange(s)[None] < tails[:, None]
    g = np.where(valid[..., None, None], g, 0.0)
    beta = np.where(valid[..., None], beta, 0.0)
    state = rng.normal(size=(b, h, D, D)).astype(np.float32)
    return (q, k, v, g, beta), state, valid


_step = jax.jit(kda.kda_step)


def _step_by_step(inputs, state):
    outs = []
    for t in range(inputs[0].shape[1]):
        o, state = _step(*(x[:, t] for x in inputs), state)
        outs.append(o)
    return np.stack(outs, axis=1), np.asarray(state)


def _assert_recurrence(o, after, want_o, want_state, valid):
    mask = valid[..., None, None]
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.where(mask, np.asarray(o) - want_o, 0.0)).max() < O_BOUND
    assert np.abs(np.asarray(after) - want_state).max() < STATE_BOUND


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(kda, 'span_backend', lambda: 'interpret')


# the chunk and sub-block ``span_form`` chooses, and two smaller tilings of
# the chip's sweep, one of them with two heads a grid step
@pytest.mark.parametrize('form, heads', [
    ((kda.KERNEL_CHUNK, kda.KERNEL_SUB_BLOCK, 1), 1),
    ((32, 8, 2), 2), ((16, 8, 1), 1),
])
def test_kernel_is_the_recurrence_at_any_tiling(form, heads):
    inputs, state, valid = _inputs(h=heads)
    want_o, want_state = _step_by_step(inputs, state)
    o, after = kda.span_kernel(*inputs, state, form=form, interpret=True)
    assert o.shape == want_o.shape and o.dtype == after.dtype == jnp.float32
    _assert_recurrence(o, after, want_o, want_state, valid)


def test_kernel_refuses_a_tiling_that_does_not_fit():
    inputs, state, _ = _inputs(b=1, s=48, h=2)
    for form in [(48, 20, 1), (48, 12, 1), (48, 16, 3), (32, 16, 1)]:
        with pytest.raises(ValueError, match='does not tile'):
            kda.span_kernel(*inputs, state, form=form, interpret=True)


@pytest.mark.parametrize('cuts', [(5, 30), (33,)])
def test_kernel_carries_its_state_over_uneven_spans(interpreted, cuts):
    inputs, state, valid = _inputs(seed=1, s=40)
    want_o, want_state = _step_by_step(inputs, state)
    edges = (0, *cuts, inputs[0].shape[1])
    outs = []
    for lo, hi in zip(edges, edges[1:]):
        o, state = kda.kda_span(*(x[:, lo:hi] for x in inputs), state)
        outs.append(np.asarray(o))
    _assert_recurrence(
        np.concatenate(outs, 1), state, want_o, want_state, valid
    )


def test_a_tail_that_does_not_count_keeps_the_state(interpreted):
    inputs, state, valid = _inputs(seed=2, s=24, counted=(0, 9))
    _, after = kda.kda_span(*inputs, state)
    np.testing.assert_array_equal(np.asarray(after[0]), state[0])
    _, want = _step_by_step(tuple(x[:, :9] for x in inputs), state)
    assert np.abs(np.asarray(after[1]) - want[1]).max() < STATE_BOUND


def test_a_long_chunk_of_the_strongest_decay_stays_finite(interpreted):
    """64 steps at -1.6 a step: a factor taken out of the difference would
    be ``exp(102)``; off the diagonal both factors' exponents stay under
    zero, on it the difference is formed first."""
    inputs, state, valid = _inputs(seed=3, counted=(None, None))
    inputs = (*inputs[:3], np.full_like(inputs[3], -1.6), inputs[4])
    want_o, want_state = _step_by_step(inputs, state)
    o, after = kda.kda_span(*inputs, state)
    _assert_recurrence(o, after, want_o, want_state, valid)


def test_beta_near_two_through_a_whole_chunk(interpreted):
    """The solve's worst case: every ``|A[t, j]|`` as large as it gets."""
    inputs, state, valid = _inputs(seed=4, counted=(None, None))
    g = -np.exp(np.random.default_rng(4).uniform(
        np.log(1e-3), np.log(0.1), size=inputs[3].shape
    )).astype(np.float32)  # slow decays: nothing fades inside the chunk
    inputs = (*inputs[:3], g, np.full_like(inputs[4], 1.99))
    want_o, want_state = _step_by_step(inputs, state)
    o, after = kda.kda_span(*inputs, state)
    _assert_recurrence(o, after, want_o, want_state, valid)


def test_bfloat16_operands_give_what_their_float32_casts_give():
    (q, k, v, g, beta), state, _ = _inputs(seed=5, b=1, s=16)
    q, k, v = (jnp.asarray(t, jnp.bfloat16) for t in (q, k, v))
    kernel = lambda q, k, v: kda.span_kernel(  # noqa: E731
        q, k, v, g, beta, state, form=(16, 8, 1), interpret=True
    )
    o, after = kernel(q, k, v)
    want_o, want = kernel(*(t.astype(jnp.float32) for t in (q, k, v)))
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(after), np.asarray(want))


def test_one_row_inside_a_scan_over_spans(interpreted, monkeypatch):
    """The cell's check calls ``kda_span`` so: one row, a span a step of a
    ``lax.scan``, the state carried. (A small tiling: the interpreter's
    program is built a second time inside the scan.)"""
    monkeypatch.setattr(kda, 'span_form', lambda *shape: (16, 8, 1))
    inputs, state, valid = _inputs(seed=6, b=1, s=48)
    want_o, want_state = _step_by_step(inputs, state)

    def one_span(carry, xs):
        o, carry = kda.kda_span(*(t[None] for t in xs), carry)
        return carry, o[0]

    spans = tuple(
        jnp.asarray(t[0]).reshape(3, 16, *t.shape[2:]) for t in inputs
    )
    after, o = jax.jit(
        lambda state, spans: jax.lax.scan(one_span, state, spans)
    )(jnp.asarray(state), spans)
    _assert_recurrence(
        np.asarray(o).reshape(want_o.shape), after, want_o, want_state, valid
    )


# --------------------------------------------------------- the form's choice
def test_span_form_is_the_scan_off_a_tpu_and_for_odd_head_sizes():
    assert kda.span_backend() == 'xla'  # the tests run on the CPU
    assert kda.span_form('xla', 4, 512, 64, 128, 128) == 'xla'
    for d_k, d_v in [(64, 64), (128, 96), (192, 128), (8, 6)]:
        assert kda.span_form('pallas', 4, 512, 64, d_k, d_v) == 'xla'


def test_kda_span_follows_the_form(monkeypatch):
    """No kernel on this backend; the kernel, at the form's tiling and on
    the interpreter, where the tests say ``'interpret'``."""
    inputs, state, _ = _inputs(b=1, s=4)
    calls = []
    monkeypatch.setattr(kda, 'span_kernel', lambda *a, **kw: (
        calls.append((a[0].shape, kw)) or (a[2].astype(jnp.float32), a[5])
    ))
    kda.kda_span(*inputs, state)
    assert not calls
    monkeypatch.setattr(kda, 'span_backend', lambda: 'interpret')
    o, _ = kda.kda_span(*inputs, state)
    form = (kda.KERNEL_CHUNK, kda.KERNEL_SUB_BLOCK, 1)
    # the span padded to the kernel's chunk on the way in, cut on the way out
    assert calls == [
        ((1, kda.KERNEL_CHUNK, 1, D), {'form': form, 'interpret': True})
    ]
    assert o.shape == (1, 4, 1, D)


@pytest.mark.parametrize('rows', [1, 2, 4])
def test_span_form_names_the_kernel_at_the_cells_prefill_shapes(rows):
    """``solar-open2-250b``'s three prefill programs on a described v5e:
    spans of 512, 64 heads of 128."""
    for backend in ('pallas', 'interpret'):
        form = kda.span_form(backend, rows, 512, 64, 128, 128)
        assert form == (kda.KERNEL_CHUNK, kda.KERNEL_SUB_BLOCK, 4)
        chunk, sub, heads = form
        assert 512 % chunk == chunk % sub == sub % 8 == 64 % heads == 0
    # fewer heads: as many a grid step as divide them
    assert kda.span_form('pallas', rows, 512, 6, 128, 128)[2] == 2
    assert kda.span_form('pallas', rows, 512, 3, 128, 256)[2] == 1


def test_engine_lists_the_span_form_of_its_prefill_programs():
    _, _, engine = make_engine()
    forms = engine.telemetry['kda_span_form']
    # the toy's heads are 8 wide: the scan, in every prefill program
    assert set(forms) == {
        key for key in engine.telemetry['moe_form'] if key.startswith('prefill')
    }
    assert forms and set(forms.values()) == {'xla'}
