"""What only a model that decides blocks of positions together asks of the
engine (``CacheSpec.block``; the contract every family shares:
``tests/test_engine_families*.py``, its row ``tests/sdar_toy.py``): what a
window covers and emits, the sizes and settings refused by name, a row's
unmask threshold, a stop token inside a block, the counters, and the cost
model's account of a window from its forwards."""

import numpy as np
import pytest
import sdar_toy as toy

from benchmarks import reference_sdar as ref
from distllm_tpu.generate.engine.engine import Request, SamplingParams
from distllm_tpu.observability import instruments
from distllm_tpu.observability.roofline import CostModel

GREEDY = dict(temperature=0.0)


@pytest.fixture(scope='module')
def served():
    return toy.make_engine()


@pytest.mark.parametrize('prompt, out, max_tokens, unacked, want', [
    (17, 0, 14, 0, (8, 7, 1)),   # a first window: 2 blocks, one token given
    (16, 0, 14, 0, (8, 8, 0)),
    (17, 7, 14, 0, (8, 7, 0)),   # 24 tokens: aligned; 7 left: two blocks, cut
    (17, 0, 14, 7, (8, 7, 0)),   # the same with the first window in flight
    (17, 11, 14, 0, (4, 3, 0)),  # 3 left: one block decided whole, then cut
    (17, 14, 14, 0, (0, 0, 0)),  # nothing left
    (3, 0, 2, 0, (8, 2, 3)),     # under a block: all of it given, two more
    (30, 5, 14, 0, (8, 5, 3)),   # a preempted request: 35 tokens, 3 given again
])
def test_a_window_covers_whole_blocks_and_emits_what_is_left(
    served, prompt, out, max_tokens, unacked, want
):
    engine = served[2]
    request = Request(
        request_id=10_000, prompt_ids=[5] * prompt,
        params=SamplingParams(max_tokens=max_tokens, **GREEDY),
    )
    request.output_ids = [6] * out
    assert engine._block_cover(request, unacked, engine.config.decode_steps) == want
    assert engine._window_budget(request, unacked, 8) == want[1]
    assert engine._prefill_end(request) == (prompt + out) // 4 * 4


@pytest.mark.parametrize('setting, value', [
    ('decode_steps', 6), ('block_size', 6), ('max_model_len', 98),
    ('prefill_chunk_tokens', 10), ('denoise_steps', 5),
])
def test_sizes_that_are_not_whole_blocks_are_refused_by_name(setting, value):
    with pytest.raises(ValueError, match=f'{setting}={value} cannot serve a model that decides'):
        toy.make_engine(**{setting: value})


def test_deferred_prefill_is_refused_by_name():
    with pytest.raises(ValueError, match='defer_prefill cannot serve a model that decides blocks'):
        toy.make_engine(defer_prefill=True)


@pytest.mark.parametrize('steps, threshold', [(2, None), (1, None), (4, 0.02)])
def test_denoise_steps_and_a_rows_threshold_reach_the_window(steps, threshold):
    """``EngineConfig.denoise_steps`` is the program's static, a request's
    ``unmask_threshold`` its row's: both read as the reference's loop."""
    hf, params, engine = toy.make_engine(denoise_steps=steps)
    prompts = [toy.prompt(np.random.default_rng(2), n) for n in (9, 22)]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(max_tokens=10, unmask_threshold=threshold, **GREEDY)
    )
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    decided = [r['decided_at'] for r in records if r['kind'] == 'request']
    for prompt, output in zip(prompts, outputs):
        want, at = ref.generate(params, hf, prompt, 10, steps, threshold)
        assert output == want and at in decided
    windows = [r for r in records if r['kind'] == 'decode']
    assert sum(r['forwards'] for r in windows) == (steps + 1) * sum(
        r['blocks'] for r in windows
    )
    if threshold is not None:  # it decided more than a position a step
        assert any(len(set(at)) < 4 for at in decided)


def test_a_stop_token_ends_a_request_behind_it(served):
    hf, params, engine = served
    prompt = toy.prompt(np.random.default_rng(7), 13)
    (free,) = engine.generate_ids([prompt], SamplingParams(max_tokens=12, **GREEDY))
    stop = free[5]
    (cut,) = engine.generate_ids(
        [prompt], SamplingParams(max_tokens=12, stop_token_ids=(stop,), **GREEDY)
    )
    # cut behind the stop token, which ``generate_ids`` strips as ever
    assert cut == free[:free.index(stop)]


def test_the_counters_add_up_a_windows_forwards_and_positions(served):
    engine = served[2]
    forwards = instruments.DENOISE_FORWARDS.value
    decided = instruments.BLOCK_POSITIONS_DECIDED.value
    before = engine.flight.total_recorded
    engine.generate_ids(
        [toy.prompt(np.random.default_rng(1), 10)], SamplingParams(max_tokens=6, **GREEDY)
    )
    windows = [
        r for r in engine.flight.snapshot()[before - engine.flight.total_recorded:]
        if r['kind'] == 'decode'
    ]
    assert instruments.DENOISE_FORWARDS.value - forwards == sum(r['forwards'] for r in windows) == 10
    assert instruments.BLOCK_POSITIONS_DECIDED.value - decided == sum(r['decided'] for r in windows) == 6
    # a window's cost is its forwards': priced, and under the peaks
    assert all(0 < r['mfu'] < 1 and 0 < r['bw_util'] < 1 for r in windows)


def test_the_cost_model_counts_a_window_from_forwards_and_positions():
    model = CostModel(1e9, 2e9, decode_steps=8, peak_flops=1e12, peak_hbm_bytes=1e11)
    plain = model.step_cost('decode', tokens=384, batch=48)
    assert (plain.flops, plain.hbm_bytes) == (2e9 * 384, 2e9 * 8)
    blocks = model.step_cost(
        'decode', tokens=377, batch=48, weight_passes=10, positions=1920
    )
    # ten forwards read the weights ten times and compute 1920 positions,
    # whatever the window emitted
    assert (blocks.flops, blocks.hbm_bytes) == (2e9 * 1920, 2e9 * 10)
