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


@pytest.fixture(scope='module')
def toy_telemetry():
    return make_engine()[2].telemetry


@pytest.mark.parametrize('key', ['kda_span_form', 'kda_inputs_form'])
def test_engine_lists_the_forms_of_its_prefill_programs(toy_telemetry, key):
    forms = toy_telemetry[key]
    # the toy's heads are 8 wide: the scan and the XLA way in, in every
    # prefill program
    assert set(forms) == {
        key for key in toy_telemetry['moe_form'] if key.startswith('prefill')
    }
    assert forms and set(forms.values()) == {'xla'}


# ------------------------------------------- the way in: q, k, v in one pass
# The kernel against the XLA form (``solar_open2._qkv_xla``, the definition):
# the largest difference over a head's largest element. The two differ by
# float32 rounding alone (the order of a 128-term sum, a root, a logistic).
WAY_IN_BOUND = 2e-6


def _way_in_operands(seed, b, s, h, taps, dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    projected = tuple(
        (jax.random.normal(k, (b, s, h * D)) * 0.8).astype(dtype)
        for k in keys[:3]
    )
    conv0 = jax.random.normal(keys[3], (b, taps - 1, 3 * h * D)).astype(dtype)
    weights = jax.random.normal(keys[4], (taps, 3 * h * D)) * taps ** -0.5
    return projected, conv0, weights.astype(dtype)


def _way_in_xla(projected, conv0, weights):
    from distllm_tpu.models import solar_open2

    cfg = solar_open2.SolarOpen2Config(
        kda_heads=projected[0].shape[-1] // D, kda_head_dim=D,
        kda_conv=weights.shape[0],
    )
    qkv = jnp.concatenate(projected, axis=-1)
    window = jnp.concatenate([conv0, qkv], axis=1)
    return tuple(
        t.reshape(*t.shape[:2], -1) for t in solar_open2._qkv_xla(
            window, {'conv': {'taps': weights}}, cfg, qkv.shape[1]
        )
    )


def _way_in_kernel(projected, conv0, weights, form=None):
    b, s, width = projected[0].shape
    form = form or kda.inputs_form(
        'interpret', b, s, 3 * width, weights.shape[0], D
    )
    return kda.inputs_kernel(
        projected, conv0, weights, form=form, head=D, q_scale=D ** -0.5,
        eps=1e-6, interpret=True,
    )


def _assert_way_in(got, want):
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == jnp.float32
        a, b = (np.asarray(t).reshape(*t.shape[:2], -1, D) for t in (a, b))
        assert np.isfinite(a).all()
        assert (np.abs(a - b).max(-1) / np.abs(b).max(-1)).max() < WAY_IN_BOUND


@pytest.mark.parametrize('rows, taps, dtype', [
    (1, 4, jnp.bfloat16), (4, 4, jnp.bfloat16), (1, 9, jnp.float32),
])
def test_way_in_kernel_is_the_xla_form(rows, taps, dtype):
    """Rows 1 and 4, the published four taps and the most the kernel
    takes, in the cell's dtype and in float32; two heads a grid
    step, two channel tiles a third."""
    operands = _way_in_operands(rows + taps, rows, 16, 4, taps, dtype)
    _assert_way_in(_way_in_kernel(*operands), _way_in_xla(*operands))


@pytest.mark.parametrize('form', [(16, 16, D), (32, 8, D)])
def test_way_in_kernel_carries_its_rows(form):
    """Two spans, the second behind the first's last rows, against one of
    twice the length; inside a span the rows pass from one sequence tile to
    the next and from one step of the loop to the next."""
    projected, conv0, weights = _way_in_operands(3, 2, 64, 1, 4)
    want = _way_in_xla(projected, conv0, weights)
    first = tuple(t[:, :32] for t in projected)
    carried = jnp.concatenate(first, axis=-1)[:, -3:]
    got = [
        _way_in_kernel(first, conv0, weights, form),
        _way_in_kernel(
            tuple(t[:, 32:] for t in projected), carried, weights, form
        ),
    ]
    _assert_way_in([jnp.concatenate(pair, axis=1) for pair in zip(*got)], want)


def test_way_in_kernel_refuses_what_it_does_not_tile():
    projected, conv0, weights = _way_in_operands(4, 1, 32, 2, 4)
    for form, head in [((32, 16, 96), 96), ((24, 8, D), D), ((32, 12, D), D),
                       ((32, 16, 3 * D), D), ((32, 16, 64), 64)]:
        with pytest.raises(ValueError, match='does not tile'):
            kda.inputs_kernel(
                projected, conv0, weights, form=form, head=head,
                q_scale=1.0, eps=1e-6, interpret=True,
            )
    with pytest.raises(ValueError, match='does not tile'):
        _way_in_kernel(projected, conv0[:, :2], weights)


def test_way_in_kernel_one_row_inside_a_scan_over_spans():
    """The cell's check calls ``_kda_inputs`` so: one row, a span a step of
    a ``lax.scan``, the convolution rows carried, fusions on either side."""
    projected, conv0, weights = _way_in_operands(5, 3, 16, 2, 4)

    def walk(way_in):
        def one_span(conv, xs):
            p = tuple((t * 1.25)[None] for t in xs)  # a neighbour before
            q, k, v = way_in(p, conv, weights)
            rows = jnp.concatenate([conv, jnp.concatenate(p, -1)], axis=1)
            return rows[:, -3:], (jnp.tanh(q[0]) * 2.0, k[0] + 1.0, v[0])

        return jax.jit(
            lambda projected, conv0: jax.lax.scan(
                one_span, conv0[:1], projected
            )
        )(projected, conv0)

    want_rows, want = walk(_way_in_xla)
    got_rows, got = walk(_way_in_kernel)
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 4 * WAY_IN_BOUND


def test_inputs_form_is_the_xla_form_where_the_kernel_has_nothing_to_tile():
    cell = dict(channels=3 * 64 * 128, taps=4, head=128)
    assert kda.inputs_form('xla', 4, 512, **cell) == 'xla'  # off a TPU
    for backend in ('pallas', 'interpret'):
        # a decode step's one position, the check's ragged call
        assert kda.inputs_form(backend, 128, 1, **cell) == 'xla'
        assert kda.inputs_form(backend, 1, 255, **cell) == 'xla'
        # a head that is not whole lane tiles, channels that are not three
        # thirds of whole heads, more carried rows than a sublane tile
        assert kda.inputs_form(backend, 4, 512, 3 * 64 * 64, 4, 64) == 'xla'
        assert kda.inputs_form(backend, 4, 512, 4 * 128, 4, 128) == 'xla'
        assert kda.inputs_form(backend, 4, 512, 3 * 128, 10, 128) == 'xla'
        assert kda.inputs_form(backend, 4, 512, 3 * 128, 1, 128) == 'xla'


@pytest.mark.parametrize('rows', [1, 2, 4])
def test_inputs_form_names_the_kernel_at_the_cells_prefill_shapes(rows):
    for backend in ('pallas', 'interpret'):
        form = kda.inputs_form(backend, rows, 512, 3 * 64 * 128, 4, 128)
        assert form == (512, kda.INPUTS_ROWS_A_STEP, 256)
        tile, step, width = form
        assert 512 % tile == tile % step == step % 8 == 8192 % width == 0
    # the largest sequence tile that divides the span, a step no longer
    # than it; an odd count of heads: one a grid step
    assert kda.inputs_form('pallas', rows, 48, 3 * 128, 4, 128) == (16, 16, 128)
    assert kda.inputs_form('pallas', rows, 1280, 9 * 128, 4, 128) == (256, 128, 128)


@pytest.fixture(scope='module')
def wide_layer():
    """One KDA layer at the published head size and toy widths: ``(cfg,
    lp)``, every leaf normal(0, 0.5) in the model's dtype."""
    from distllm_tpu.models import solar_open2

    cfg = solar_open2.SolarOpen2Config(
        hidden_size=32, kda_heads=2, kda_head_dim=D, kda_conv=4
    )
    shapes = solar_open2._tree_shapes(cfg, 'kda')
    keys = jax.random.split(jax.random.PRNGKey(1), len(shapes))
    return cfg, {
        name: solar_open2._wrap(
            name, (jax.random.normal(key, shape) * 0.5).astype(cfg.dtype)
        )
        for key, (name, shape) in zip(keys, shapes.items())
    }


@pytest.mark.parametrize('span, kernel', [(32, True), (1, False)])
def test_kda_inputs_follows_the_form(wide_layer, monkeypatch, span, kernel):
    """``_kda_inputs`` with the kernel where the rule names it and the XLA
    form where it does not (a decode step): the same six
    returns, ``q, k, v`` inside the bound, the rest equal."""
    from distllm_tpu.models import solar_open2

    cfg, lp = wide_layer
    keys = jax.random.split(jax.random.PRNGKey(span), 2)
    u = jax.random.normal(keys[0], (2, span, cfg.hidden_size)).astype(cfg.dtype)
    conv0 = jax.random.normal(keys[1], (2, 3, 6 * D)).astype(cfg.dtype)
    want = solar_open2._kda_inputs(u, lp, cfg, conv0)  # this backend: XLA
    calls = []
    kernel_fn = kda.inputs_kernel
    monkeypatch.setattr(kda, 'inputs_kernel', lambda *a, **kw: (
        calls.append(kw['form']) or kernel_fn(*a, **kw)
    ))
    monkeypatch.setattr(kda, 'span_backend', lambda: 'interpret')
    got = solar_open2._kda_inputs(u, lp, cfg, conv0)
    assert calls == ([(32, 32, 2 * D)] if kernel else [])
    flat = lambda t: t.reshape(*t.shape[:2], -1)  # noqa: E731
    _assert_way_in([flat(t) for t in got[:3]], [flat(t) for t in want[:3]])
    for a, b in zip(got[3:], want[3:]):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize('span', [2, 40])
def test_conv_rows_are_conv_tails(span):
    """The mixer's rows for the next span, cut from the narrow window, are
    ``common.conv_tail``'s of the whole one at every tail: none counted,
    fewer than the carried rows, the whole span."""
    from distllm_tpu.models import common, solar_open2

    window = jax.random.normal(jax.random.PRNGKey(span), (5, 3 + span, 12))
    tails = jnp.asarray([0, 1, 2, span - 1, span][:5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(solar_open2._conv_rows(window, tails, 3)),
        np.asarray(common.conv_tail(window, tails, 3)),
    )


def test_prefill_forms_lists_both_rules(wide_layer, monkeypatch):
    cfg, _ = wide_layer
    programs = {'prefill(512, 4)': (512, 4), 'prefill(8, 1)': (8, 1)}
    assert cfg.prefill_forms(programs) == {
        'kda_span_form': dict.fromkeys(programs, 'xla'),
        'kda_inputs_form': dict.fromkeys(programs, 'xla'),
    }
    monkeypatch.setattr(kda, 'span_backend', lambda: 'pallas')
    assert cfg.prefill_forms(programs) == {
        'kda_span_form': dict.fromkeys(programs, (64, 32, 2)),
        'kda_inputs_form': {
            'prefill(512, 4)': (512, 128, 256), 'prefill(8, 1)': 'xla',
        },
    }
