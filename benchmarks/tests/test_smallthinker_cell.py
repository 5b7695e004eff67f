"""The cell PR 52 adds (``smallthinker-21b-a3b.batch_reasoning``): its byte
account against the issue's arithmetic, its files against what the issue
states, its readers on hand-made records, and a CPU rehearsal through the
harness at toy sizes (``rehearsal_smallthinker``; never a measurement)."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import smallthinker_bytes, traffic
from benchmarks.manifest import Manifest
from benchmarks.readers import smallthinker

ROOT = Path(__file__).resolve().parents[2]
CELL = 'smallthinker-21b-a3b.batch_reasoning'
MODEL = json.loads(
    (ROOT / 'benchmarks/configs/smallthinker-21b-a3b.json').read_text()
)
NEW_METRICS = {
    'model.smallthinker_decode_step_ms.batch',
    'model.smallthinker_decode_bw_share.batch',
    'kernel.paged_attn_roofline_share.smallthinker',
    'model.moe_route_time_share.batch',
    'engine.kv_window_pool_held_share.batch',
    'engine.rows_under_window_share.batch',
}


def test_byte_account_matches_the_issues_arithmetic():
    # 2.626 G parameters held, 389 M of them the embedding that is not
    # streamed: a decode step reads 4.47 GB of weights.
    assert smallthinker_bytes.weight_params(MODEL) == (
        16 * 115_512_320 + 151_936 * 2560 + 2560
    )
    assert smallthinker_bytes.layers_of(MODEL) == {'full': 4, 'window': 12}
    assert smallthinker_bytes.kv_bytes_per_token_layer(MODEL) == 2048
    # 8 KiB a token in the full group, 24 KiB in the window group.
    assert smallthinker_bytes.kv_bytes(MODEL, 1, 0) == 4 * 2048
    assert smallthinker_bytes.kv_bytes(MODEL, 0, 1) == 12 * 2048
    # 4 x 28 x 128 operations a cached token a layer: far under the ridge.
    assert smallthinker_bytes.attn_flops(MODEL, 1, 1) == 16 * 4 * 28 * 128
    assert (
        smallthinker_bytes.attn_flops(MODEL, 1, 1) / 197e12
        < smallthinker_bytes.kv_bytes(MODEL, 1, 1) / 819e9
    )
    # 48 rows at a mean context of 5800, 3400 of it inside the window: about
    # 10.6 GB a step, 13 ms at 819 GB/s.
    step = smallthinker_bytes.decode_step_bytes(MODEL, 48 * 5800, 48 * 3400, 48)
    assert step == pytest.approx(10.6e9, rel=0.03)


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    entry = next(
        c for c in manifest.data['configs'] if c['name'] == 'smallthinker-21b-a3b'
    )
    assert entry['source'] == MODEL['source'] and entry['reduced'] == MODEL['reduced']
    assert (MODEL['num_hidden_layers'], MODEL['moe_num_primary_experts'],
            MODEL['num_routed_experts'], MODEL['vocab_size']) == (16, 16, 64, 151936)
    assert MODEL['published']['moe_num_primary_experts'] == 64
    assert len(MODEL['assumed']) >= 7
    assert '4 pipeline stages' in MODEL['deployment'] and '4 v5e chips' in MODEL['deployment']
    assert {'max_num_seqs', 'num_blocks'} <= set(MODEL['engine_notes'])
    assert MODEL['engine']['max_model_len'] == 16384
    assert MODEL['engine']['prefill_chunk_tokens'] == 512
    assert MODEL['engine']['prefill_min_bucket'] == 512
    assert MODEL['expect_attn_backend'] == 'pallas'
    cell = manifest.cell(CELL)
    assert cell['chips'] == 1 and cell['config'] == 'smallthinker-21b-a3b'
    assert 'its share' in cell['why']
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'smallthinker_closed'
    spec = workload['traffic']
    assert spec['prompts_per_call'] == 48 and spec['schedule_seed'] == 0
    assert spec['prompt_tokens'] == {'dist': 'loguniform', 'lo': 1024, 'hi': 15360}
    assert spec['output_tokens'] == {'dist': 'fixed', 'value': 1024}
    assert workload['sampling'] == {'temperature': 0.5, 'top_p': 0.95}
    assert {m['name'] for m in manifest.metrics_of('end_to_end', CELL)} == {
        'gen_tok_s', 'setup_s',
    }
    reported = {m['name'] for m in manifest.metrics_of('per_layer', CELL)}
    assert NEW_METRICS <= reported and 'model.moe_held_pair_share.batch' in reported
    for name in NEW_METRICS:  # each a data file whose reader is there
        module, func = manifest.load('metrics', name)['reader'].split(':')
        assert hasattr(__import__(f'benchmarks.readers.{module}', fromlist=['x']), func)


def test_the_check_scores_rows_under_across_and_past_the_window():
    """Of the call's 48 prompts the 8 the check scores, evenly spaced by
    length: at least two stay under the window with their 1024 tokens out,
    one crosses it while it decodes, three start past it; and the call as a
    whole is what the issue reckoned."""
    workload = json.loads(
        (ROOT / f'benchmarks/workloads/{CELL}.json').read_text()
    )['traffic']
    lengths = sorted(traffic.sizes(
        workload['prompt_tokens'], 48, traffic.schedule_rng(workload, 'call')
    ))
    scored = [lengths[round(j * 47 / 7)] for j in range(8)]
    out, window = workload['output_tokens']['value'], MODEL['sliding_window_size']
    assert sum(n + out <= window for n in scored) >= 2
    assert sum(n <= window < n + out for n in scored) == 1
    assert sum(n > window for n in scored) >= 3
    assert lengths[0] >= 1024 and lengths[-1] + out <= MODEL['engine']['max_model_len']
    assert sum(lengths) == pytest.approx(254e3, rel=0.01)
    # rows under the window at a call's start: two fifths
    assert np.mean([n <= window for n in lengths]) == pytest.approx(0.5, abs=0.03)
    assert np.mean([n + out <= window for n in lengths]) == pytest.approx(0.4, abs=0.03)


def _ctx(capture=None):
    return SimpleNamespace(config=MODEL, device_kind='TPU v5e', capture=capture)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the fields (the parent commit), or a run without a
    traced slice, leaves the metric out and raises nothing."""
    old = {'kind': 'decode', 'batch': 4, 'tokens': 32, 'kv_blocks': 90,
           'kv_blocks_full': 90, 'kv_blocks_window': 30}
    for flight in ([{'kind': 'decode', 'batch': 4, 'kv_blocks': 90}], [old]):
        obs = {'flight': flight, 'counters': {}, 'trace': None}
        assert smallthinker.window_pool_held_share(_ctx(), obs) is None
        assert smallthinker.rows_under_window_share(_ctx(), obs) is None
        assert smallthinker.decode_bw_share(_ctx(), obs, '^jit_smallthinker') is None
        assert smallthinker.paged_attn_roofline_share(_ctx(), obs, 'x') is None
        traced = dict(obs, kernel_call_s={}, trace={
            'busy_s': 1.0, 'op_s': {}, 'module_s': {}, 'module_n': {}})
        assert smallthinker.decode_bw_share(_ctx(), traced, '^jit_smallthinker') is None
        assert smallthinker.paged_attn_roofline_share(_ctx(), traced, 'x') is None


def test_readers_read_the_counters_and_the_kernels_calls():
    windows = [
        {'kind': 'decode', 'batch': 48, 'tokens': 384, 't0_s': 10.0 + i,
         'kv_blocks_full': 18000, 'kv_blocks_window': 10000,
         'kv_window_pool_blocks': 12508, 'rows_under_window': 20 - i}
        for i in range(3)
    ]
    capture = SimpleNamespace(t_start=10.5, t_stop=12.5)  # holds two of them
    program = 'jit_smallthinker_window_fn(123)'
    obs = {
        'flight': windows, 'counters': {},
        'trace': {'busy_s': 4.0, 'module_s': {program: 0.48},
                  'module_n': {program: 3}, 'op_s': {}},
        'kernel_call_s': {
            f'{program} distllm.attn_full': 0.10,
            f'{program} distllm.attn_window': 0.14,
            f'{program} distllm.moe': 9.0,  # the grouped matmul: not read
            'jit_smallthinker_prefill_fn(9) distllm.attn_full': 5.0,
        },
    }
    assert smallthinker.window_pool_held_share(_ctx(), obs) == pytest.approx(
        100 * 10000 / 12508
    )
    assert smallthinker.rows_under_window_share(_ctx(), obs) == pytest.approx(
        100 * 57 / 144
    )
    # 0.48 s over 3 runs x 8 steps = 20 ms a step.
    moved = smallthinker_bytes.decode_step_bytes(MODEL, 16 * 18000, 16 * 10000, 48)
    share = smallthinker.decode_bw_share(_ctx(), obs, '^jit_smallthinker_window_fn')
    assert share == pytest.approx(100 * moved / 819e9 / 0.02) and 50 < share < 100
    pattern = Manifest(ROOT / 'BENCHMARK.json').load(
        'metrics', 'kernel.paged_attn_roofline_share.smallthinker'
    )['args']['pattern']
    asked = 2 * 8 * smallthinker_bytes.kv_bytes(MODEL, 16 * 18000, 16 * 10000)
    roofline = smallthinker.paged_attn_roofline_share(_ctx(capture), obs, pattern)
    assert roofline == pytest.approx(100 * asked / 819e9 / 0.24) and roofline < 100


def _rehearse(trace_flag: int, tree: Path = ROOT) -> subprocess.CompletedProcess:
    manifest = tree / 'benchmarks/tests/rehearsal_smallthinker/BENCHMARK.json'
    return subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-smallthinker.batch_reasoning', '--seed', '3000000007', '--seconds',
         '1', '--trace', str(trace_flag), '--allow-cpu', '--manifest', str(manifest)],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS='cpu'),
        cwd=tree, timeout=900,
    )


def test_rehearsal_of_the_cell():
    done = _rehearse(1)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['failed'] == 0 < line['attempted']
    assert line['metrics'] == {}
    detail = line['detail']
    assert detail['compiles_in_window'] == 0 and len(detail['token_gap_by_row']) == 6
    # float32 on both sides here
    assert detail['token_gap_row_median_std'] <= detail['token_gap_max_std'] < 1e-3
    assert detail['token_gap_mean_std'] < 1e-3
    assert set(detail['kv_pools']) == {'full', 'window'}
    # layer 0's pages of the full group and layer 1's of the window group
    assert detail['kv_content_error'] < 1e-5 > detail['kv_window_content_error']
    assert len(detail['kv_content_error_by_row']) == 6
    assert detail['check_preemptions'] == 0
    assert detail['window_engine']['preemptions'] == 0
    assert {'weights', 'engine', 'warmup_calls', 'programs',
            'reference_ahead'} <= set(detail['setup_split_s'])
    traced = line['rehearsal_metrics']
    # What needs no device trace reads on the CPU too.
    assert {'engine.decode_occupancy.batch', 'engine.window_host_ms.batch',
            'model.moe_held_pair_share.batch', 'engine.kv_window_held_share.batch',
            'engine.kv_window_pool_held_share.batch',
            'engine.rows_under_window_share.batch'} <= set(traced)
    assert 30 < traced['model.moe_held_pair_share.batch']['value'] < 70
    assert 0 < traced['engine.kv_window_pool_held_share.batch']['value'] < 100
    assert 0 < traced['engine.rows_under_window_share.batch']['value'] < 100


def test_the_cell_fails_at_once_without_the_model_module(tmp_path):
    """On a checkout that lacks ``models/smallthinker.py`` (the parent
    commit) the driver's first import fails: exit code non-zero, nothing
    allocated, no result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(
        ROOT / 'distllm_tpu', tree / 'distllm_tpu',
        ignore=shutil.ignore_patterns(
            'smallthinker.py', '__pycache__', '_build', '*.so'
        ),
    )
    (tree / 'distllm_tpu/models/__init__.py').write_text('')
    done = _rehearse(0, tree)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'smallthinker' in done.stderr
