"""CPU tests of the benchmark's own code. Run with
``python -m pytest benchmarks/tests -q`` (tier-1 collects ``tests/`` only).

No test here reads a time or a rate: the rehearsals only check that each
driver runs end to end at a toy size and prints the contract's last line.
"""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import peaks, reduce, trace, traffic  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402

DATA = Path(__file__).parent / 'data'
REHEARSAL = Path(__file__).parent / 'rehearsal' / 'BENCHMARK.json'
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')


# ------------------------------------------------------- trace reduction
@pytest.fixture(scope='module')
def recorded():
    return json.loads((DATA / 'trace_embed_small.json').read_text())


def test_interval_arithmetic():
    merged = trace.union([(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)])
    assert merged == [(0, 20), (30, 45)]
    assert trace.total(merged) == 35
    assert trace.gaps_between(merged) == [(20, 30)]


def test_self_times_count_each_instant_once():
    events = [
        ['while', 0, 100, ''], ['a', 10, 30, ''], ['b', 50, 40, ''],
        ['c', 200, 10, ''],
    ]
    assert trace.self_times(events) == {'while': 30, 'a': 30, 'b': 40, 'c': 10}


def test_gap_goes_to_the_span_that_covers_most_of_it():
    spans = [['bench:a', 0, 12, ''], ['distllm:decode', 12, 100, '']]
    assert trace.attribute((10, 30), spans) == 'distllm:decode'
    assert trace.attribute((200, 300), spans) == 'unattributed'


def test_recorded_trace_busy_idle_and_attribution(recorded):
    """The recorded slice: one forward program (265.4 ms), the concatenate,
    97.7 ms idle between two passes, the next pass's first forward
    (147.6 ms)."""
    summary = trace.summarize(recorded)
    assert summary['devices'] == 1
    assert summary['busy_s'] == pytest.approx(0.413204468, abs=1e-9)
    assert summary['span_s'] == pytest.approx(0.510893614, abs=1e-9)
    # idle share of the slice, as the driver works it out from busy and window
    assert 1 - summary['busy_s'] / summary['span_s'] == pytest.approx(0.1912, abs=1e-4)
    # one gap over 20 us, inside the second pass's host span
    assert summary['gap_s'] == {'bench:pass': pytest.approx(0.097657926, abs=1e-9)}
    assert summary['small_gap_s'] < 1e-4
    # busy + gaps = span
    assert summary['busy_s'] + summary['host_gap_s'] + summary['small_gap_s'] == (
        pytest.approx(summary['span_s'], abs=1e-9)
    )


def test_recorded_trace_kernel_and_program_time_by_name(recorded):
    summary = trace.summarize(recorded)
    # self times add up to the busy time: the enclosing while is not counted twice
    assert sum(summary['op_s'].values()) == pytest.approx(summary['busy_s'], abs=1e-9)
    kernel = trace.seconds_matching(
        summary['op_s'], r'^%encoder_attention\S* custom-call'
    )
    assert kernel == pytest.approx(0.06658983, abs=1e-8)
    assert trace.seconds_matching(summary['module_s'], r'^jit__fused') == (
        pytest.approx(0.265445008 + 0.147615881, abs=1e-9)
    )
    assert trace.seconds_matching(summary['module_n'], r'^jit__fused') == 2
    top = trace.top(summary['op_s'], 3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]


def test_inner_span_wins_over_the_harness_span_around_it():
    spans = [['bench:generate_ids', 0, 1000, ''], ['distllm:prefill', 100, 60, '']]
    assert trace.attribute((110, 150), spans) == 'distllm:prefill'
    assert trace.attribute((400, 500), spans) == 'bench:generate_ids'
    # an inner span that covers less than half of the gap does not take it
    assert trace.attribute((100, 400), spans) == 'bench:generate_ids'


def test_recorded_engine_trace_programs_kernels_and_gaps():
    """The recorded slice of ``mistral7b.chat_steady``: the end of a decode
    window, 5.07 ms idle while the host fetched its tokens, a dense prefill
    of 2 x 512 tokens with its KV scatter and sampler, 4.45 ms idle that no
    annotation covers, the start of the next window."""
    recorded = json.loads((DATA / 'trace_engine_small.json').read_text())
    summary = trace.summarize(recorded)
    assert summary['busy_s'] == pytest.approx(0.100279576, abs=1e-9)
    assert summary['gap_s'] == {
        'distllm:fetch': pytest.approx(0.005074823, abs=1e-9),
        'unattributed': pytest.approx(0.004449612, abs=1e-9),
    }
    # programs by name: the whole XLA Modules line (three decode windows)
    window_s = trace.seconds_matching(summary['module_s'], r'^jit_window_fn')
    runs = trace.seconds_matching(summary['module_n'], r'^jit_window_fn')
    assert runs == 3
    assert 1e3 * window_s / (runs * 8) == pytest.approx(30.508, abs=1e-3)  # ms a step
    prefill = trace.seconds_matching(summary['module_s'], r'^jit__?(write_)?prefill')
    assert prefill == pytest.approx(0.083376597 + 0.000691364, abs=1e-9)
    # the Pallas paged-attention kernel, which the trace names closed_call.<n>
    kernel = trace.seconds_matching(summary['op_s'], r'^%closed_call\S* custom-call')
    assert kernel == pytest.approx(0.002465455, abs=1e-9)


def test_op_names_split_into_name_opcode_and_type():
    assert trace._split_op(
        '%closed_call.14 = bf16[1,8,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
        'custom-call(s32[1,256]{1,0:T(1,128)} %x), custom_call_target="tpu"'
    ) == ('%closed_call.14', 'custom-call bf16[1,8,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)}')
    name, detail = trace._split_op(
        '%sort.9 = (f32[32,32768]{1,0}, s32[32,32768]{1,0}) sort(f32[32,32768] %a)'
    )
    assert name == '%sort.9' and detail.startswith('sort (f32[32,32768]')


def test_trace_without_device_ops_reduces_to_nothing():
    host_only = {'planes': [{'name': '/host:CPU', 'lines': [
        {'name': 'python3', 'events': [['bench:pass', 0, 10, '']]}]}]}
    assert trace.summarize(host_only) is None


# ------------------------------------------------------------ arithmetic
def test_percentiles_are_exact():
    values = [5, 1, 4, 2, 3]
    assert reduce.percentile(values, 0.0) == 1
    assert reduce.percentile(values, 0.5) == 3
    assert reduce.percentile(values, 1.0) == 5
    assert reduce.percentile(values, 0.95) == pytest.approx(4.8)
    data = np.random.default_rng(0).exponential(size=257)
    for q in (0.5, 0.9, 0.95, 0.99):
        assert reduce.percentile(data, q) == pytest.approx(np.quantile(data, q))
    with pytest.raises(ValueError):
        reduce.percentile([], 0.5)


def test_peaks_table_and_shape_functions():
    assert peaks.device_peaks('TPU v5 lite') == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks.device_peaks('TPU v9')
    mistral = json.loads((ROOT / 'benchmarks/configs/mistral7b.json').read_text())
    # 7.11e9 streamed parameters (all but the embedding table) in bf16
    assert peaks.decoder_weight_bytes(mistral) == pytest.approx(14.22e9, rel=1e-3)
    assert peaks.decoder_kv_bytes_per_token(mistral) == 131072
    bert = json.loads((ROOT / 'benchmarks/configs/pubmedbert.json').read_text())
    one = peaks.encoder_flops(bert, tokens_real=1, sum_sq_len=1)
    assert one == 12 * (2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 768)


# --------------------------------------------------------------- traffic
@pytest.mark.parametrize('spec', [
    {'dist': 'loguniform', 'lo': 64, 'hi': 1536},
    {'dist': 'loguniform', 'lo': 16, 'hi': 192},
    {'dist': 'uniform', 'lo': 120, 'hi': 260},
])
def test_sizes_stay_in_range_and_every_seed_gets_the_same_set(spec):
    a = traffic.sizes(spec, 200, traffic.rng_for(1, 's'))
    b = traffic.sizes(spec, 200, traffic.rng_for(3_000_000_007, 's'))
    assert min(a) >= spec['lo'] and max(a) <= spec['hi']
    assert sorted(a) == sorted(b) and a != b
    if spec['dist'] == 'loguniform':
        # log-uniform: the median is the geometric mean of the ends
        assert np.median(a) == pytest.approx(
            math.sqrt(spec['lo'] * (spec['hi'] + 1)), rel=0.05
        )
        assert np.mean(a) > np.median(a)  # a tail to the right


def test_same_seed_same_workload():
    spec = {
        'prompt_tokens': {'dist': 'loguniform', 'lo': 8, 'hi': 64},
        'output_tokens': {'dist': 'loguniform', 'lo': 2, 'hi': 8},
    }

    def build(seed):
        at = traffic.poisson_arrivals(4.0, 10.0, traffic.rng_for(seed, 'arrivals'))
        return traffic.requests(spec, len(at), 512, seed, 'requests', arrivals=at)

    assert build(7) == build(7)
    assert build(7) != build(8)
    reqs = build(2**31 + 5)  # a seed beyond 32 signed bits
    assert len(reqs) == 40
    assert all(0 < r.at_s < 10.0 for r in reqs)
    assert all(4 <= t < 512 for r in reqs for t in r.prompt_ids)
    texts = traffic.corpus(
        {'count': 16, 'words': {'dist': 'uniform', 'lo': 5, 'hi': 9}, 'vocab_words': 50}, 3
    )
    assert texts == traffic.corpus(
        {'count': 16, 'words': {'dist': 'uniform', 'lo': 5, 'hi': 9}, 'vocab_words': 50}, 3
    )
    assert all(5 <= len(t.split()) <= 9 for t in texts)


def test_calls_of_a_closed_loop_share_one_order_of_sizes():
    spec = {
        'prompt_tokens': {'dist': 'loguniform', 'lo': 8, 'hi': 64},
        'output_tokens': {'dist': 'fixed', 'value': 4},
    }

    def call(stream, order_stream=None):
        return traffic.requests(spec, 24, 512, 11, stream, order_stream=order_stream)

    first, second = call('call0', 'call'), call('warmup0', 'call')
    assert [len(r.prompt_ids) for r in first] == [len(r.prompt_ids) for r in second]
    assert [r.prompt_ids for r in first] != [r.prompt_ids for r in second]
    # without it every stream has an order of its own
    assert [len(r.prompt_ids) for r in call('call0')] != [
        len(r.prompt_ids) for r in call('call1')
    ]


def test_poisson_arrivals_have_exponential_gaps():
    at = traffic.poisson_arrivals(5.0, 40.0, traffic.rng_for(1, 'a'))
    gaps = np.diff([0.0, *at])
    assert len(at) == 200 and at[-1] < 40.0
    assert np.mean(gaps) == pytest.approx(0.2, rel=0.02)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)


# -------------------------------------------------------------- manifest
def test_manifest_names_files_and_units():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    data = manifest.data
    assert set(data) == {
        'command', 'paths', 'run_seconds', 'configs', 'workloads',
        'end_to_end', 'per_layer',
    }
    names = []
    for config in data['configs']:
        names.append(config['name'])
        assert (ROOT / config['file']).is_file()
        stored = json.loads((ROOT / config['file']).read_text())
        assert stored['source'] == config['source']
        assert stored['reduced'] == config['reduced']
    e2e = {m['name'] for m in data['end_to_end']}
    assert 'setup_s' in e2e
    for cell in data['workloads']:
        names.append(cell['name'])
        assert NAME.match(cell['traffic']) and cell['chips'] in (1, 4)
        assert len(cell['why']) <= 200
        workload = manifest.load('workloads', cell['name'])
        assert workload['config'] == cell['config']
        assert (ROOT / 'benchmarks/drivers' / f"{workload['driver']}.py").is_file()
        assert len(manifest.metrics_of('end_to_end', cell['name'])) >= 2
        assert manifest.metrics_of('per_layer', cell['name'])
    for metric in data['end_to_end'] + data['per_layer']:
        names.append(metric['name'])
        assert UNIT.match(metric['unit']), metric
        assert metric['better'] in ('lower', 'higher')
        for cell in metric.get('workloads', []):
            manifest.cell(cell)
    for metric in data['end_to_end']:
        assert 0 < metric['bound'] <= 0.1
    layers = set()
    for metric in data['per_layer']:
        layers.add(metric['layer'])
        assert metric['moves'] in e2e
        spec = manifest.load('metrics', metric['name'])
        module, func = spec['reader'].split(':')
        reader = __import__(f'benchmarks.readers.{module}', fromlist=[func])
        assert callable(getattr(reader, func))
        # the metric it moves is reported wherever this one is
        moved = next(m for m in data['end_to_end'] if m['name'] == metric['moves'])
        cells = metric.get('workloads') or [c['name'] for c in data['workloads']]
        assert set(cells) <= set(moved.get('workloads', cells))
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    assert len((ROOT / 'BENCHMARK.json').read_bytes()) <= 64 * 1024


def test_the_unlisted_embed_cell_is_ready_to_list():
    """``pubmedbert.embed_corpus`` ran on the chip in PR 23 and is kept out of
    the manifest only for the memory floor (PERF.md section 7, row 0); its
    files stay whole so that a later PR lists it by adding entries."""
    base = ROOT / 'benchmarks'
    workload = json.loads((base / 'workloads/pubmedbert.embed_corpus.json').read_text())
    config = json.loads((base / 'configs/pubmedbert.json').read_text())
    assert workload['config'] == 'pubmedbert' and config['reduced'] == []
    assert (base / 'drivers' / f"{workload['driver']}.py").is_file()
    for name in ('embed.padding_share', 'embed.host_gap_share',
                 'embed.compiles_in_window', 'model.encoder_flops_share',
                 'kernel.encoder_attn_time_share'):
        spec = json.loads((base / 'metrics' / f'{name}.json').read_text())
        module, func = spec['reader'].split(':')
        reader = __import__(f'benchmarks.readers.{module}', fromlist=[func])
        assert callable(getattr(reader, func))


# ------------------------------------------------------------ rehearsals
LAST_LINE_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


def _rehearse(cell: str, trace_flag: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, str(ROOT / 'benchmarks/run.py'), '--workload', cell,
         '--seed', '3000000007', '--seconds', '1', '--trace', str(trace_flag),
         '--allow-cpu', '--manifest', str(REHEARSAL)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize('cell,expects', [
    ('tiny-bert.embed_corpus', {'emb_per_s', 'setup_s'}),
    ('tiny-mistral.batch_generate', {'gen_tok_s', 'setup_s'}),
    ('tiny-mistral.chat_steady', {'ttft_p95_ms', 'tpot_p95_ms', 'setup_s'}),
])
def test_rehearsal_prints_the_contracts_last_line(cell, expects):
    line = _rehearse(cell, 0)
    assert LAST_LINE_KEYS <= set(line)
    assert line['correct'] is True and line['failed'] == 0 < line['attempted']
    assert set(line['device']) >= {'platform', 'kind', 'count', 'memory_peak_bytes'}
    # a CPU run writes nothing under a device metric's name
    assert line['device']['platform'] == 'cpu' and line['metrics'] == {}
    assert set(line['rehearsal_metrics']) == expects
    for value in line['rehearsal_metrics'].values():
        assert value['value'] > 0 and UNIT.match(value['unit'])


def test_closed_loop_calls_repeat_the_warm_up_and_describe_themselves():
    detail = _rehearse('tiny-mistral.batch_generate', 0)['detail']
    # the replica call ran every program the window's calls need
    assert detail['compiles_in_window'] == 0
    calls = detail['calls']
    assert calls and all(
        {'s', 'steps_tokens', 'slowest', 'longest_gap_s'} <= set(c) for c in calls
    )
    # calls of one cell do the same work
    assert all(c['steps_tokens'] == calls[0]['steps_tokens'] for c in calls)
    assert 'gc_in_window_s' in detail


def test_rehearsal_traced_run_reports_per_layer_counters():
    line = _rehearse('tiny-mistral.chat_steady', 1)
    got = set(line['rehearsal_metrics'])
    # counters and spans are read on the CPU; trace readers find no device
    # plane and report nothing
    assert {'loadgen.lag_p95_ms', 'engine.queue_wait_p95_ms',
            'engine.window_host_ms.chat', 'engine.compiles_in_window.chat'} <= got
    assert not {'model.decode_step_ms.chat', 'model.prefill_share.chat'} & got


def test_a_run_without_a_tpu_fails():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, str(ROOT / 'benchmarks/run.py'), '--workload',
         'mistral7b.chat_steady', '--seed', '1', '--seconds', '1', '--trace', '0'],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert done.returncode != 0 and done.stdout.strip() == ''
