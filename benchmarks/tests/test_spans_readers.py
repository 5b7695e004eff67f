"""CPU tests of the step-span readers (``benchmarks/readers/spans.py``): each
on hand-made observations, including what it does with a program that
writes none of its fields, and a rehearsal of both engine cells that lists
the new metrics. No test here reads a time or a rate.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import peaks, trace  # noqa: E402
from benchmarks.readers import spans  # noqa: E402

REHEARSAL = Path(__file__).parent / 'rehearsal'
# the decode window's call of the kernel: [rows, KV heads, group, head dim]
KERNEL = r'^%closed_call\S* custom-call bf16\[32,2,2,16\]'
MODEL = {
    'hidden_size': 64, 'num_attention_heads': 4, 'num_key_value_heads': 2,
    'num_hidden_layers': 3,
    'engine': {'block_size': 16, 'decode_steps': 8},
}
NEW_METRICS = {
    'tiny-mistral.chat_steady': {
        'engine.serving_compile_ms.chat', 'engine.relowered_programs.chat',
        'engine.reprefill_share.chat', 'engine.prefill_wait_p95_ms.chat',
    },
    'tiny-mistral.batch_generate': {
        'engine.serving_compile_ms.batch', 'engine.reprefill_share.batch',
    },
}


def _ctx(t_start=100.0, t_stop=110.0, kind='TPU v5 lite'):
    capture = trace.Capture(delay_s=0.0, length_s=1.0)
    capture.t_armed, capture.t_start, capture.t_stop = 99.0, t_start, t_stop
    return SimpleNamespace(config=MODEL, capture=capture, device_kind=kind)


def _step(kind, t0, **fields):
    return {'kind': kind, 'seq': int(t0), 't0_s': t0, 't1_s': t0 + 0.2,
            'tokens': 8, **fields}


def _compile(path, seconds, **fields):
    return {'kind': 'compile', 'program': 'jit(prefill_paged_fn)',
            'duration_s': seconds, 'cache_hit': True, 'path': path, **fields}


# What a program from before the spans writes: the same kinds, none of the
# new fields.
OLD_FLIGHT = [
    {'kind': 'prefill', 'tokens': 40, 'host_s': 0.001, 'put_s': 0.001},
    {'kind': 'decode', 'tokens': 8, 'batch': 2, 'host_s': 0.001, 'put_s': 0.001},
    {'kind': 'preempt', 'rids': [3]},
    {'kind': 'compile', 'phase': 'prefill', 'shape': 'b1x16', 'duration_s': 1.0,
     'cache_hit': False},
    {'kind': 'request', 'queue_wait_s': 0.001, 'ttft_s': 0.3},
]


@pytest.mark.parametrize('reader', [
    spans.serving_compile_ms, spans.relowered_programs, spans.reprefill_share,
    spans.prefill_wait_p95_ms,
])
def test_flight_readers_report_nothing_for_a_program_without_spans(reader):
    assert reader(_ctx(), {'flight': OLD_FLIGHT, 'trace': None}) is None
    assert reader(_ctx(), {'flight': [], 'trace': None}) is None


def test_serving_compiles_are_summed_and_startup_ones_left_out():
    flight = [
        _step('decode', 101.0),
        _compile('serving', 0.25, during='prefill', seq=7, relowered=True,
                 changed=[{'arg': 'k', 'what': 'committed'}]),
        _compile('serving', 0.05, during='fetch', seq=7, relowered=False),
        _compile('serving', 0.5, during=None, seq=None),  # no call in flight
        _compile('startup', 9.0, phase='prefill', shape='b1x16'),
        {'kind': 'compile', 'phase': 'prefill', 'shape': 'b1x16',
         'duration_s': 9.5, 'cache_hit': False},  # the phase's own record
    ]
    obs = {'flight': flight, 'trace': None}
    assert spans.serving_compile_ms(_ctx(), obs) == pytest.approx(800.0)
    assert spans.relowered_programs(_ctx(), obs) == 1.0
    # a window with spans and no compile reads 0, not nothing
    quiet = {'flight': [_step('decode', 101.0)], 'trace': None}
    assert spans.serving_compile_ms(_ctx(), quiet) == 0.0
    assert spans.relowered_programs(_ctx(), quiet) == 0.0


def test_reprefill_share_is_tokens_lost_over_tokens_prefilled():
    flight = [
        _step('prefill', 101.0, tokens=300, route='dense'),
        _step('prefill', 102.0, tokens=100, route='paged'),
        {'kind': 'preempt', 'rids': [4, 9], 'tokens_lost': [30, 50], 'seq': 5},
        {'kind': 'preempt', 'rids': [2], 'tokens_lost': [20], 'seq': 8},
        _step('decode', 103.0),
    ]
    assert spans.reprefill_share(_ctx(), {'flight': flight}) == pytest.approx(25.0)
    assert spans.reprefill_share(
        _ctx(), {'flight': [_step('prefill', 101.0, tokens=64)]}
    ) == 0.0
    # decode only: no prefill work to take a share of
    assert spans.reprefill_share(_ctx(), {'flight': [_step('decode', 1.0)]}) is None


def test_prefill_wait_is_admission_to_first_token_less_own_prefill():
    def request(admit, first, own, **extra):
        return {'kind': 'request', 't_admit_s': admit, 't_first_s': first,
                'prefill_first_s': own, **extra}

    flight = [request(10.0, 10.5, 0.1) for _ in range(19)]
    # preempted after its first token: the re-prefill is no part of own
    flight.append(request(20.0, 21.0, 0.2, preemptions=1))
    flight.append({'kind': 'request', 't_admit_s': None, 't_first_s': None,
                   'prefill_first_s': 0.0})  # failed before admission
    got = spans.prefill_wait_p95_ms(_ctx(), {'flight': flight})
    assert got == pytest.approx(1e3 * (0.4 + 0.05 * 0.4))
    # a clock read in the other order never reads as a negative wait
    assert spans.prefill_wait_p95_ms(
        _ctx(), {'flight': [request(1.0, 1.1, 0.3)]}
    ) == 0.0


def test_idle_outside_spans_counts_what_no_phase_span_holds():
    summary = {'span_s': 10.0, 'busy_s': 9.0, 'op_s': {},
               'gap_s': {'distllm:plan': 0.4, 'distllm:fetch': 0.2,
                         'bench:generate_ids': 0.3, 'unattributed': 0.1,
                         # the root names no phase: it explains nothing
                         'distllm:serve': 0.2}}
    assert spans.idle_outside_spans_share(_ctx(), {'trace': summary}) == (
        pytest.approx(6.0)
    )
    assert spans.idle_outside_spans_share(_ctx(), {'trace': None}) is None
    assert spans.idle_outside_spans_share(
        _ctx(), {'trace': {'span_s': 0.0, 'gap_s': {}}}
    ) is None


def test_kernel_bandwidth_share_divides_bytes_asked_by_kernel_seconds():
    block_bytes = 16 * peaks.decoder_kv_bytes_per_token(MODEL)
    assert block_bytes == 16 * 2 * 3 * 2 * 16 * 2
    flight = [
        _step('decode', 99.0, kv_blocks=1000),    # before the slice
        _step('decode', 101.0, kv_blocks=40),     # 8 steps over its contexts
        _step('decode', 102.0, kv_blocks=60),
        # other programs' calls of the kernel: on neither side
        _step('mixed', 102.5, kv_blocks=70),
        _step('prefill', 103.0, kv_blocks=25, route='chunk'),
        _step('prefill', 104.0, route='dense'),
        _step('decode', 111.0, kv_blocks=1000),   # after it
    ]
    asked = (40 + 60) * 8 * block_bytes
    obs = {'flight': flight, 'trace': {
        'op_s': {'%closed_call.12 custom-call bf16[32,2,2,16]{3,2,1,0}': 2e-6,
                 '%closed_call.14 custom-call bf16[32,2,2,16]{3,2,1,0}': 1e-6,
                 # a chunked prefill's call, and a dense prefill's
                 '%closed_call.9 custom-call bf16[1,2,1024,16]{3,2,1,0}': 9e-6,
                 '%closed_call.3 custom-call bf16[4,512,4,16]{3,2,1,0}': 9e-6,
                 '%fusion.1 fusion bf16[32,4096]': 5.0},
    }}
    assert spans.decode_kv_bytes_asked(_ctx(), obs) == asked
    share = spans.paged_attn_bw_share(_ctx(), obs, pattern=KERNEL)
    assert share == pytest.approx(100.0 * asked / 819e9 / 3e-6)


def test_kernel_bandwidth_share_reports_nothing_without_its_inputs():
    flight = [_step('decode', 101.0, kv_blocks=40)]
    kernel = {'op_s': {'%closed_call.12 custom-call bf16[32,2,2,16]': 2e-6}}
    args = {'pattern': KERNEL}
    assert spans.paged_attn_bw_share(
        _ctx(), {'flight': flight, 'trace': None}, **args) is None
    # a trace with no such kernel in it (attention resolved to XLA)
    assert spans.paged_attn_bw_share(
        _ctx(), {'flight': flight, 'trace': {'op_s': {'%fusion.1 fusion': 1.0}}},
        **args) is None
    # a program that does not count blocks
    assert spans.paged_attn_bw_share(
        _ctx(), {'flight': OLD_FLIGHT, 'trace': kernel}, **args) is None
    # no traced slice on the capture's clock
    assert spans.paged_attn_bw_share(
        _ctx(t_start=None, t_stop=None), {'flight': flight, 'trace': kernel},
        **args) is None


# ------------------------------------------------------------ rehearsals
def _rehearsal_manifest(tmp_path) -> Path:
    """The rehearsal's manifest with the root manifest's new entries, their
    cells renamed to the toy cells."""
    manifest = json.loads((REHEARSAL / 'BENCHMARK.json').read_text())
    manifest['paths'] = [str(REHEARSAL)]
    listed = {m['name'] for m in manifest['per_layer']}
    root = json.loads((ROOT / 'BENCHMARK.json').read_text())
    for entry in root['per_layer']:
        spec = json.loads(
            (ROOT / 'benchmarks/metrics' / f"{entry['name']}.json").read_text()
        )
        if not spec['reader'].startswith('spans:') or entry['name'] in listed:
            continue
        manifest['per_layer'].append({**entry, 'workloads': [
            w.replace('mistral7b.', 'tiny-mistral.') for w in entry['workloads']
        ]})
    path = tmp_path / 'BENCHMARK.json'
    path.write_text(json.dumps(manifest))
    return path


@pytest.mark.parametrize('cell', sorted(NEW_METRICS))
def test_rehearsal_lists_the_span_metrics(cell, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, str(ROOT / 'benchmarks/run.py'), '--workload', cell,
         '--seed', '3000000007', '--seconds', '1', '--trace', '1',
         '--allow-cpu', '--manifest', str(_rehearsal_manifest(tmp_path))],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['metrics'] == {}
    got = line['rehearsal_metrics']
    # counters and spans are read on the CPU; the two trace readers find no
    # device plane and report nothing
    assert NEW_METRICS[cell] <= set(got)
    assert not {'kernel.paged_attn_bw_share.batch',
                'engine.idle_outside_spans_share.batch',
                'engine.idle_outside_spans_share.chat'} & set(got)
    for name in NEW_METRICS[cell]:
        assert got[name]['value'] >= 0.0
    if cell.endswith('batch_generate'):
        # the replica call compiled every program the window's calls need
        assert got['engine.serving_compile_ms.batch']['value'] == 0.0


def test_the_manifest_lists_every_span_metric_with_its_cell():
    root = json.loads((ROOT / 'BENCHMARK.json').read_text())
    by_name = {m['name']: m for m in root['per_layer']}
    for cell, names in NEW_METRICS.items():
        for name in names:
            assert by_name[name]['workloads'] == [
                cell.replace('tiny-mistral.', 'mistral7b.')
            ]
    for name in ('engine.idle_outside_spans_share.batch',
                 'engine.idle_outside_spans_share.chat',
                 'kernel.paged_attn_bw_share.batch'):
        assert by_name[name]['source'] == 'device_trace'
    assert by_name['kernel.paged_attn_bw_share.batch']['layer'] == 'kernels'
