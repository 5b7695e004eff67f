"""The cell PR 30 adds (``laguna-xs.2.batch_mixed_lengths``): its byte
account against the issue's arithmetic, its files against what the issue
states, its readers on hand-made records, and a CPU rehearsal through the
harness at toy sizes (``rehearsal_laguna``; never a measurement)."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import laguna_bytes
from benchmarks.manifest import Manifest
from benchmarks.readers import laguna

ROOT = Path(__file__).resolve().parents[2]
REHEARSAL = ROOT / 'benchmarks/tests/rehearsal_laguna/BENCHMARK.json'
CELL = 'laguna-xs.2.batch_mixed_lengths'
MODEL = json.loads((ROOT / 'benchmarks/configs/laguna-xs.2.json').read_text())


def test_byte_account_matches_the_issues_arithmetic():
    # 4.76 B parameters held, 51 M of them the embedding that is not streamed.
    assert laguna_bytes.weight_params(MODEL) == pytest.approx(4.76e9 - 51.4e6, rel=2e-3)
    assert laguna_bytes.layers_of(MODEL) == {'full': 5, 'window': 15}
    assert laguna_bytes.kv_bytes_per_token_layer(MODEL) == 4096
    # 20 KiB a token in the full group, 60 KiB in the window group.
    assert laguna_bytes.kv_bytes(MODEL, 1, 0) == 5 * 4096
    assert laguna_bytes.kv_bytes(MODEL, 0, 1) == 15 * 4096
    # 48 rows at a mean context of 3000: 2.9 GB of full-group KV, 1.6 GB of
    # windows, beside 9.4 GB of weights: 17 ms at 819 GB/s.
    step = laguna_bytes.decode_step_bytes(MODEL, 48 * 3000, 48 * 544)
    assert step == pytest.approx(13.97e9, rel=0.02)


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    entry = next(c for c in manifest.data['configs'] if c['name'] == 'laguna-xs.2')
    assert entry['source'] == MODEL['source'] and entry['reduced'] == MODEL['reduced']
    assert set(MODEL['reduced']) == {
        'num_hidden_layers', 'layer_types', 'mlp_layer_types',
        'num_attention_heads_per_layer', 'num_experts', 'vocab_size',
    }
    assert (MODEL['num_hidden_layers'], MODEL['num_experts'],
            MODEL['num_routed_experts'], MODEL['vocab_size']) == (20, 64, 256, 25088)
    assert MODEL['published']['num_experts'] == 256 and len(MODEL['assumed']) >= 4
    assert '2 pipeline stages' in MODEL['deployment'] and '4 v5e chips' in MODEL['deployment']
    assert MODEL['engine']['max_model_len'] == 8448
    assert MODEL['engine']['prefill_chunk_tokens'] == 512
    assert MODEL['expect_attn_backend'] == 'pallas'
    cell = manifest.cell(CELL)
    assert cell['chips'] == 1 and cell['config'] == 'laguna-xs.2'
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'laguna_closed'
    traffic = workload['traffic']
    assert traffic['prompts_per_call'] == 48 and traffic['schedule_seed'] == 0
    assert traffic['prompt_tokens'] == {'dist': 'loguniform', 'lo': 512, 'hi': 8192}
    assert traffic['output_tokens'] == {'dist': 'fixed', 'value': 256}
    assert workload['sampling'] == {'temperature': 0.5, 'top_p': 0.95}
    assert {m['name'] for m in manifest.metrics_of('end_to_end', CELL)} == {
        'gen_tok_s', 'setup_s',
    }
    assert {m['name'] for m in manifest.metrics_of('per_layer', CELL)} == {
        'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
        'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
        'model.moe_time_share.batch', 'model.moe_held_pair_share.batch',
        'model.laguna_decode_step_ms.batch', 'model.laguna_decode_bw_share.batch',
        'kernel.full_attn_time_share.batch', 'kernel.window_attn_time_share.batch',
        'kernel.paged_attn_bw_share.laguna', 'engine.kv_window_held_share.batch',
    }
    # The kernel's pattern names the decode window's result types.
    spec = manifest.load('metrics', 'kernel.paged_attn_bw_share.laguna')
    seqs = MODEL['engine']['max_num_seqs']
    assert f'bf16\\[{seqs},8,[68],128\\]' in spec['args']['pattern']


def _ctx(capture=None):
    return SimpleNamespace(config=MODEL, device_kind='TPU v5e', capture=capture)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the fields (the parent commit), or a run without a
    traced slice, leaves the metric out and raises nothing."""
    obs = {'flight': [{'kind': 'decode', 'batch': 4, 'tokens': 32, 'kv_blocks': 90}],
           'counters': {}, 'trace': None}
    assert laguna.window_held_share(_ctx(), obs) is None
    assert laguna.decode_bw_share(_ctx(), obs, '^jit_laguna_window_fn') is None
    assert laguna.paged_attn_bw_share(_ctx(), obs, 'custom-call') is None
    traced = dict(obs, trace={'busy_s': 1.0, 'op_s': {}, 'module_s': {}, 'module_n': {}})
    assert laguna.decode_bw_share(_ctx(), traced, '^jit_laguna_window_fn') is None
    assert laguna.paged_attn_bw_share(_ctx(), traced, 'custom-call') is None


def test_readers_read_the_groups_block_counts():
    windows = [
        {'kind': 'decode', 'batch': 48, 'tokens': 384, 't0_s': 10.0 + i,
         'kv_blocks_full': 9000, 'kv_blocks_window': 1632}
        for i in range(3)
    ]
    capture = SimpleNamespace(t_start=10.5, t_stop=12.5)  # holds two of them
    kernel = '%distllm.attn_full.47 custom-call bf16[48,8,6,128]{3,2,1,0}'
    obs = {
        'flight': windows, 'counters': {},
        'trace': {
            'busy_s': 4.0, 'module_s': {'jit_laguna_window_fn(1)': 1.2},
            'module_n': {'jit_laguna_window_fn(1)': 3},
            'op_s': {kernel: 0.2, '%distllm.attn_full.9 custom-call bf16[4,8,3072,128]{3,2,1,0}': 1.0},
        },
    }
    assert laguna.window_held_share(_ctx(), obs) == pytest.approx(100 * 1632 / 9000)
    # 1.2 s over 3 runs x 8 steps = 50 ms a step.
    bytes_moved = laguna_bytes.decode_step_bytes(MODEL, 16 * 9000, 16 * 1632)
    share = laguna.decode_bw_share(_ctx(), obs, '^jit_laguna_window_fn')
    assert share == pytest.approx(100 * bytes_moved / 819e9 / 0.05) and share < 100
    asked = 2 * 8 * laguna_bytes.kv_bytes(MODEL, 16 * 9000, 16 * 1632)
    pattern = Manifest(ROOT / 'BENCHMARK.json').load(
        'metrics', 'kernel.paged_attn_bw_share.laguna')['args']['pattern']
    assert laguna.paged_attn_bw_share(_ctx(capture), obs, pattern) == pytest.approx(
        100 * asked / 819e9 / 0.2
    )


def _rehearse(trace_flag: int, tree: Path = ROOT) -> subprocess.CompletedProcess:
    manifest = tree / 'benchmarks/tests/rehearsal_laguna/BENCHMARK.json'
    return subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-laguna.batch_mixed_lengths', '--seed', '3000000007', '--seconds', '1',
         '--trace', str(trace_flag), '--allow-cpu', '--manifest', str(manifest)],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS='cpu'),
        cwd=tree, timeout=900,
    )


def test_rehearsal_of_the_cell():
    done = _rehearse(0)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['failed'] == 0 < line['attempted']
    assert line['metrics'] == {} and set(line['rehearsal_metrics']) == {'gen_tok_s', 'setup_s'}
    detail = line['detail']
    assert detail['compiles_in_window'] == 0 and len(detail['token_gap_by_row']) == 6
    # float32 on both sides here
    assert detail['token_gap_row_median_std'] <= detail['token_gap_max_std'] < 1e-3
    assert set(detail['kv_pools']) == {'full', 'window'}
    # the two limits of the second round: the mean gap, and layer 0's K and
    # V pages of every scored row against float32
    assert detail['token_gap_mean_std'] < 1e-3
    assert detail['kv_content_error'] < 1e-5 and len(detail['kv_content_error_by_row']) == 6
    # where set-up went, and the two engine metrics this cell cannot list
    assert {'weights', 'engine', 'warmup_calls', 'phases', 'programs',
            'programs_from_cache', 'programs_s'} <= set(detail['setup_split_s'])
    assert detail['window_engine'] == {
        'reprefill_share': 0.0, 'serving_compile_ms': 0.0, 'budget_deferrals': 0,
    }
    calls = detail['calls']
    assert calls and all(c['steps_tokens'] == calls[0]['steps_tokens'] for c in calls)
    done = _rehearse(1)
    assert done.returncode == 0, done.stderr[-2000:]
    traced = json.loads(done.stdout.strip().splitlines()[-1])['rehearsal_metrics']
    # What needs no device trace reads on the CPU too.
    assert {'engine.decode_occupancy.batch', 'engine.window_host_ms.batch',
            'engine.compiles_in_window.batch', 'model.moe_held_pair_share.batch',
            'engine.kv_window_held_share.batch'} <= set(traced)
    assert 30 < traced['model.moe_held_pair_share.batch']['value'] < 70
    assert 0 < traced['engine.kv_window_held_share.batch']['value'] < 100


def test_the_cell_fails_at_once_without_the_model_module(tmp_path):
    """On a checkout that lacks ``models/laguna.py`` (the parent commit) the
    driver's first import fails: exit code non-zero, nothing allocated, no
    result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(ROOT / 'distllm_tpu', tree / 'distllm_tpu',
                    ignore=shutil.ignore_patterns('laguna.py', '__pycache__', '_build', '*.so'))
    init = tree / 'distllm_tpu/models/__init__.py'
    init.write_text('')  # the parent's table has no such row either
    done = _rehearse(0, tree)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'laguna' in done.stderr
