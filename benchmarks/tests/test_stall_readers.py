"""CPU tests of the stall readers (``benchmarks/readers/stalls.py``): each
on hand-made records (no stall, one stall, a compile, the tracer's own
stop), what they do with a program that writes none of the fields, and a
rehearsal of both engine cells that lists the six metrics. No test here
reads a time or a rate.
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

from benchmarks import trace  # noqa: E402
from benchmarks.readers import stalls  # noqa: E402

REHEARSAL = Path(__file__).parent / 'rehearsal'
CLOSED = [
    'mistral7b.batch_generate', 'granite-4.0-h-small.batch_generate',
    'laguna-xs.2.batch_mixed_lengths', 'kanana-2-30b-a3b.batch_mixed_lengths',
    'lfm2-8b-a1b.batch_mixed_lengths_wide', 'falcon-h1-34b.batch_generate',
    'solar-open2-250b.batch_long_documents', 'ouro-2.6b.batch_mcqa',
]
METRICS = {
    'engine.stall_s': ('stalls:stall_s', 's', 'program_span'),
    'engine.stalls_in_window': (
        'stalls:stalls_in_window', 'stalls', 'program_counter',
    ),
    'engine.serve_self_share': ('stalls:serve_self_share', '%', 'program_span'),
}
READERS = (stalls.stall_s, stalls.stalls_in_window, stalls.serve_self_share)


def _ctx(t_start=None, t_stop=None):
    capture = trace.Capture(delay_s=0.0, length_s=1.0)
    capture.t_armed, capture.t_start, capture.t_stop = 99.0, t_start, t_stop
    return SimpleNamespace(capture=capture)


def _step(kind, seq, t0, t1, **fields):
    return {'kind': kind, 'seq': seq, 't0_s': t0, 't1_s': t1,
            'serve_self_s': 0.001, 'fetch_s': 0.1, **fields}


def _stall(seq, span, t_edge, age, sample=0, compiling=False):
    return {'kind': 'stall', 'seq': seq, 'span': span, 'thread': 'MainThread',
            't_edge_s': t_edge, 't_s': t_edge + age, 'age_s': age,
            'sample': sample, 'compiling': compiling,
            'stacks': [{'thread': 'MainThread', 'frames': ['x.py:1 f']}]}


def _obs(flight, window_s=10.0):
    return {'flight': flight, 'window_s': window_s, 'trace': None}


# What a program from before the watcher writes: the step records, none of
# the new fields.
OLD_FLIGHT = [
    {'kind': 'prefill', 'seq': 1, 't0_s': 100.0, 't1_s': 100.2, 'tokens': 40},
    {'kind': 'decode', 'seq': 2, 't0_s': 100.2, 't1_s': 100.5, 'fetch_s': 0.1},
    {'kind': 'request', 'queue_wait_s': 0.001},
]
CLEAN = [
    _step('prefill', 1, 100.0, 100.2),
    _step('decode', 2, 100.2, 100.5),
    _step('decode', 3, 100.4, 100.7, serve_self_s=0.002),
    {'kind': 'request', 'queue_wait_s': 0.001},
]


@pytest.mark.parametrize('reader', READERS)
def test_a_program_without_the_watcher_reports_nothing(reader):
    assert reader(_ctx(), _obs(OLD_FLIGHT)) is None
    assert reader(_ctx(), _obs([])) is None


def test_no_stall_is_a_reading():
    assert stalls.stall_s(_ctx(), _obs(CLEAN)) == 0.0
    assert stalls.stalls_in_window(_ctx(), _obs(CLEAN)) == 0.0
    assert stalls.serve_self_share(_ctx(), _obs(CLEAN)) == pytest.approx(
        100.0 * 0.004 / 10.0
    )


def test_one_stall_is_its_seconds_and_one_count_whatever_the_samples():
    flight = CLEAN + [
        _stall(4, 'fetch', 101.0, 1.2),
        _stall(4, 'fetch', 101.0, 2.5, sample=1),
        _step('decode', 4, 100.8, 104.3, stalled_s=3.1, fetch_s=3.2,
              stalled_edge_s=101.0),
    ]
    assert stalls.stall_s(_ctx(), _obs(flight)) == pytest.approx(3.1)
    assert stalls.stalls_in_window(_ctx(), _obs(flight)) == 1.0
    # a traced slice elsewhere in the window leaves both as they are
    assert stalls.stall_s(_ctx(95.0, 99.5), _obs(flight)) == pytest.approx(3.1)
    assert stalls.stalls_in_window(_ctx(95.0, 99.5), _obs(flight)) == 1.0


def test_a_compile_counts_as_no_stall_and_keeps_its_seconds():
    flight = CLEAN + [
        _stall(4, 'decode', 101.0, 1.2, compiling=True),
        _step('decode', 4, 100.8, 104.3, stalled_s=3.1, dispatch_s=3.2,
              stalled_edge_s=101.0),
    ]
    assert stalls.stalls_in_window(_ctx(), _obs(flight)) == 0.0
    # ``stalled_s`` is what the thread stood still, whatever the cause
    assert stalls.stall_s(_ctx(), _obs(flight)) == pytest.approx(3.1)


def test_the_tracers_own_stop_is_left_out():
    """The open loop stops its profiler between two ``step()`` calls with
    requests unfinished: a hole of tens of seconds that begins right before
    ``t_stop`` is read and ends where the next step opens, whose record
    takes the seconds and the hole's edge. Neither reader counts it; a
    stall of the program's in the same window stays."""
    hole = [
        _stall(5, None, 110.0, 1.1),
        _stall(5, None, 110.0, 2.3, sample=1),
        _step('decode', 6, 130.001, 130.3, stalled_s=20.0, stalled_edge_s=110.0),
    ]
    own = [
        _stall(7, 'fetch', 140.0, 1.5),
        _step('decode', 7, 139.9, 142.2, stalled_s=2.2, stalled_edge_s=140.0),
    ]
    flight = CLEAN + hole + own
    assert stalls.stalls_in_window(_ctx(), _obs(flight)) == 2.0
    assert stalls.stall_s(_ctx(), _obs(flight)) == pytest.approx(22.2)
    traced = _ctx(t_start=105.0, t_stop=110.0002)
    assert stalls.stalls_in_window(traced, _obs(flight)) == 1.0
    assert stalls.stall_s(traced, _obs(flight)) == pytest.approx(2.2)
    # a start that takes long: t_start is read at the hole's END
    traced = _ctx(t_start=129.9995, t_stop=150.0)
    assert stalls.stalls_in_window(traced, _obs(flight)) == 1.0
    assert stalls.stall_s(traced, _obs(flight)) == pytest.approx(2.2)


def test_serve_self_share_needs_a_window():
    assert stalls.serve_self_share(_ctx(), _obs(CLEAN, window_s=0.0)) is None


# ------------------------------------------------------------ the manifest
def test_the_manifest_appends_the_six_with_file_reader_and_cells():
    root = json.loads((ROOT / 'BENCHMARK.json').read_text())
    last = root['per_layer'][-6:]
    names = [f'{base}.{suffix}' for base in METRICS for suffix in ('batch', 'chat')]
    assert [m['name'] for m in last] == names
    for entry in last:
        base, _, suffix = entry['name'].rpartition('.')
        reader, unit, source = METRICS[base]
        spec = json.loads(
            (ROOT / 'benchmarks/metrics' / f"{entry['name']}.json").read_text()
        )
        assert spec['name'] == entry['name'] and spec['reader'] == reader
        assert entry['unit'] == unit and entry['source'] == source
        assert entry['better'] == 'lower' and entry['layer'] == 'serving engine'
        if suffix == 'batch':
            assert entry['workloads'] == CLOSED
            assert entry['moves'] == 'gen_tok_s'
        else:
            assert entry['workloads'] == ['mistral7b.chat_steady']
            assert entry['moves'] == 'ttft_p95_ms'


# ------------------------------------------------------------ rehearsals
def _rehearsal_manifest(tmp_path) -> Path:
    """The rehearsal's manifest with the six entries, their cells the toy
    cells."""
    manifest = json.loads((REHEARSAL / 'BENCHMARK.json').read_text())
    manifest['paths'] = [str(REHEARSAL)]
    root = json.loads((ROOT / 'BENCHMARK.json').read_text())
    for entry in root['per_layer'][-6:]:
        cell = ('tiny-mistral.batch_generate' if entry['name'].endswith('.batch')
                else 'tiny-mistral.chat_steady')
        manifest['per_layer'].append({**entry, 'workloads': [cell]})
    path = tmp_path / 'BENCHMARK.json'
    path.write_text(json.dumps(manifest))
    return path


@pytest.mark.parametrize('cell,suffix', [
    ('tiny-mistral.batch_generate', 'batch'),
    ('tiny-mistral.chat_steady', 'chat'),
])
def test_rehearsal_lists_the_stall_metrics(cell, suffix, tmp_path):
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
    for base in METRICS:
        assert got[f'{base}.{suffix}']['value'] >= 0.0, base
    # the loop's own lines are a small part of a window
    assert got[f'engine.serve_self_share.{suffix}']['value'] < 50.0
