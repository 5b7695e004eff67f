"""CPU tests of the set-up readers (``benchmarks/readers/setup.py``): each of
the nine metrics over a hand-written account, what they do with a program
that keeps none, the manifest's nine entries, and one rehearsal that prints
all nine. No test here reads a time or a rate.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import trace  # noqa: E402
from benchmarks.readers import setup  # noqa: E402

REHEARSAL = Path(__file__).parent / 'rehearsal'
CELLS = ['mistral7b.batch_generate', 'mistral7b.chat_steady']
# metric -> (reader, its ``what``, unit, source)
METRICS = {
    'setup.before_engine_s': ('seconds', 'before_engine_s', 's', 'program_span'),
    'setup.engine_init_s': ('seconds', 'engine_init_s', 's', 'program_span'),
    'setup.warmup_run_s': ('seconds', 'warmup_run_s', 's', 'program_span'),
    'setup.trace_lower_s': ('seconds', 'trace_lower_s', 's', 'program_span'),
    'setup.cache_load_s': ('seconds', 'cache_load_s', 's', 'program_span'),
    'setup.compile_miss_s': ('seconds', 'compile_miss_s', 's', 'program_span'),
    'setup.programs': ('count', 'programs', 'programs', 'program_counter'),
    'setup.cache_miss_programs': (
        'count', 'cache_miss_programs', 'programs', 'program_counter'),
    'setup.unphased_init_share': ('unphased_init_share', None, '%', 'program_span'),
}

# A set-up as the program's clock saw it: the process starts at 1000, the
# engine is built from 1020 to 1060, the warm-up runs to 1100, the check's
# call to 1110, where the window is armed. The harness's setup_s leaves the
# check's 10 s out: 100.
START, ARMED, SETUP_S = 1000.0, 1110.0, 100.0
WHOLE = {
    'process_start_s': START, 'until_s': ARMED,
    'before_engine_s': 20.0, 'engine_init_s': 40.0, 'after_engine_s': 50.0,
    'unphased_init_s': 3.0, 'after_engine_program_s': 19.0,
    'programs': 53, 'cache_miss_programs': 2,
    'trace_lower_s': 12.5, 'cache_load_s': 30.25, 'compile_miss_s': 7.75,
}
# The same account up to 1100: the check's programs (3 of them, 4 s) are out.
CUT = {**WHOLE, 'until_s': START + SETUP_S, 'after_engine_s': 40.0,
       'after_engine_program_s': 15.0, 'programs': 50}


class _Watcher:
    def __init__(self, summaries):
        self.asked = []
        self._summaries = summaries

    def summary(self, until_s=None):
        self.asked.append(until_s)
        return dict(self._summaries[until_s])


def _ctx(t_armed=ARMED):
    capture = trace.Capture(delay_s=0.0, length_s=0.0)
    capture.t_armed = t_armed
    return SimpleNamespace(capture=capture)


def _obs():
    return {'end_to_end': {'setup_s': SETUP_S}}


@pytest.fixture
def watcher(monkeypatch):
    from distllm_tpu.observability import startup

    fake = _Watcher({ARMED: WHOLE, START + SETUP_S: CUT})
    monkeypatch.setattr(startup, 'get_compile_watcher', lambda: fake)
    return fake


def _read(name):
    reader, what, _, _ = METRICS[name]
    args = {} if what is None else {'what': what}
    return getattr(setup, reader)(_ctx(), _obs(), **args)


def test_each_reader_over_a_hand_written_account(watcher):
    got = {name: _read(name) for name in METRICS}
    assert got == {
        'setup.before_engine_s': 20.0,
        'setup.engine_init_s': 40.0,
        # 1060 to the cut at 1100, less the 15 s of programs in between
        'setup.warmup_run_s': 25.0,
        'setup.trace_lower_s': 12.5,
        'setup.cache_load_s': 30.25,
        'setup.compile_miss_s': 7.75,
        'setup.programs': 53.0,
        'setup.cache_miss_programs': 2.0,
        'setup.unphased_init_share': 7.5,
    }
    # the account is asked for the window's start, and for set-up's end with
    # the check's call cut from it
    assert set(watcher.asked) == {ARMED, START + SETUP_S}
    # the stretches and the programs between them are the harness's setup_s
    assert (
        got['setup.before_engine_s'] + got['setup.engine_init_s']
        + got['setup.warmup_run_s'] + CUT['after_engine_program_s']
    ) == SETUP_S


@pytest.mark.parametrize('name', sorted(METRICS))
def test_a_program_without_the_account_reports_nothing(name, monkeypatch):
    from distllm_tpu.observability import startup

    # a watcher from before summary() existed
    monkeypatch.setattr(startup, 'get_compile_watcher', lambda: object())
    assert _read(name) is None
    # an account with no engine_init record
    bare = {**WHOLE, 'engine_init_s': None, 'after_engine_s': None,
            'unphased_init_s': None, 'after_engine_program_s': None}
    fake = _Watcher({ARMED: bare, START + SETUP_S: bare})
    monkeypatch.setattr(startup, 'get_compile_watcher', lambda: fake)
    assert _read(name) is None
    # a driver that never armed the capture
    reader, what, _, _ = METRICS[name]
    args = {} if what is None else {'what': what}
    assert getattr(setup, reader)(_ctx(t_armed=None), _obs(), **args) is None


def test_the_real_watcher_answers_the_readers(monkeypatch):
    """The readers against the program's own ``summary`` of a hand-made
    set-up: the keys they read are the keys it writes."""
    from distllm_tpu.observability import startup, steps

    watch = startup.CompileWatcher()
    monkeypatch.setattr(startup, 'get_compile_watcher', lambda: watch)
    with watch.phase('engine_init', 'mistral:b4'):
        time.sleep(0.002)  # a share of no seconds is no share
    armed = steps.clock()
    obs = {'end_to_end': {'setup_s': armed - startup.process_start_s()}}
    got = {}
    for name, (reader, what, _, _) in METRICS.items():
        args = {} if what is None else {'what': what}
        got[name] = getattr(setup, reader)(_ctx(armed), obs, **args)
    assert all(value is not None and value >= 0.0 for value in got.values())
    assert got['setup.programs'] == 0.0
    assert (
        got['setup.before_engine_s'] + got['setup.engine_init_s']
        + got['setup.warmup_run_s']
    ) == pytest.approx(obs['end_to_end']['setup_s'], abs=1e-3)


def test_the_manifest_lists_the_nine_with_file_reader_and_cells():
    root = json.loads((ROOT / 'BENCHMARK.json').read_text())
    by_name = {m['name']: m for m in root['per_layer']}
    assert {n for n in by_name if n.startswith('setup.')} == set(METRICS)
    # appended: they are the manifest's last nine, in this order
    assert [m['name'] for m in root['per_layer'][-9:]] == list(METRICS)
    for name, (reader, what, unit, source) in METRICS.items():
        entry = by_name[name]
        assert entry == {
            'name': name, 'unit': unit, 'better': 'lower', 'source': source,
            'layer': 'set-up', 'moves': 'setup_s', 'workloads': CELLS,
        }
        spec = json.loads(
            (ROOT / 'benchmarks/metrics' / f'{name}.json').read_text()
        )
        assert spec['name'] == name and spec['reader'] == f'setup:{reader}'
        assert spec.get('args', {}) == ({} if what is None else {'what': what})
        assert callable(getattr(setup, reader)) and len(spec['what']) > 40


# ------------------------------------------------------------ rehearsal
def _rehearsal_manifest(tmp_path) -> Path:
    """The rehearsal's manifest with the root manifest's set-up entries,
    their cells renamed to the toy cells."""
    manifest = json.loads((REHEARSAL / 'BENCHMARK.json').read_text())
    manifest['paths'] = [str(REHEARSAL)]
    root = json.loads((ROOT / 'BENCHMARK.json').read_text())
    for entry in root['per_layer']:
        if entry['name'] in METRICS:
            manifest['per_layer'].append({**entry, 'workloads': [
                w.replace('mistral7b.', 'tiny-mistral.')
                for w in entry['workloads']
            ]})
    path = tmp_path / 'BENCHMARK.json'
    path.write_text(json.dumps(manifest))
    return path


def test_rehearsal_prints_all_nine_and_counts_the_harness_programs(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, str(ROOT / 'benchmarks/run.py'), '--workload',
         'tiny-mistral.batch_generate', '--seed', '3600000007', '--seconds',
         '1', '--trace', '1', '--allow-cpu', '--manifest',
         str(_rehearsal_manifest(tmp_path))],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['metrics'] == {}
    got = line['rehearsal_metrics']
    assert set(METRICS) <= set(got)
    for name, (_, _, unit, _) in METRICS.items():
        assert got[name]['unit'] == unit and got[name]['value'] >= 0.0
    # the program's records and the harness's own listener heard the same
    # events
    assert got['setup.programs']['value'] == line['detail']['setup_programs']
    assert got['setup.programs']['value'] > 0
    assert got['setup.unphased_init_share']['value'] <= 100.0
