"""The cell PR 32 adds (``kanana-2-30b-a3b.batch_mixed_lengths``): its byte
and operation account against the issue's arithmetic and the program's own
parameter tree, its files against what the issue states, its readers on
hand-made records, and a CPU rehearsal through the harness at toy sizes
(``rehearsal_deepseek_v3``; never a measurement)."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import deepseek_v3_bytes
from benchmarks.manifest import Manifest
from benchmarks.readers import deepseek_v3

ROOT = Path(__file__).resolve().parents[2]
CELL = 'kanana-2-30b-a3b.batch_mixed_lengths'
LAGUNA_CELL = 'laguna-xs.2.batch_mixed_lengths'
MODEL = json.loads((ROOT / 'benchmarks/configs/kanana-2-30b-a3b.json').read_text())


def test_byte_account_matches_the_issues_arithmetic_and_the_programs_tree():
    import jax

    from distllm_tpu.models import deepseek_v3 as program

    # 4,497 M parameters held, 65.7 M of them the embedding that is not streamed.
    params = deepseek_v3_bytes.weight_params(MODEL)
    assert params == pytest.approx(4.497e9 - 65.7e6, rel=2e-3)
    cfg = program.DeepseekV3Config.from_hf_config(MODEL)
    shapes = jax.eval_shape(
        lambda: program.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    held = sum(a.size for a in jax.tree.leaves(shapes))
    assert held == params + MODEL['vocab_size'] * MODEL['hidden_size']
    assert held * 2 == pytest.approx(8.99e9, rel=2e-3)
    # A cached token: 576 values a layer, stored in 640 lanes, once.
    assert deepseek_v3_bytes.stored_row(MODEL) == 640
    assert deepseek_v3_bytes.row_bytes_per_token_layer(MODEL) == 1280
    assert deepseek_v3_bytes.latent_bytes(MODEL, 1) == 24 * 1280  # 30.7 KB
    # 32 x (192 + 128) x 2 bytes of K and V would be 16 times that.
    assert 32 * (192 + 128) * 2 == 16 * 1280
    # 2 x 32 x (576 + 512) operations a cached token a layer.
    assert deepseek_v3_bytes.attn_flops(MODEL, 1) == 24 * 69632
    # 48 rows at a mean context of 3000: 4.4 GB of rows beside 8.9 GB of
    # weights: 16 ms at 819 GB/s.
    step = deepseek_v3_bytes.decode_step_bytes(MODEL, 48 * 3000)
    assert step == pytest.approx(13.29e9, rel=0.01)
    # The pool the configuration asks for, as the engine reports it.
    blocks = MODEL['engine']['num_blocks']
    assert deepseek_v3_bytes.latent_bytes(MODEL, blocks * 16) == blocks * 16 * 640 * 2 * 24


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    entry = next(c for c in manifest.data['configs'] if c['name'] == 'kanana-2-30b-a3b')
    assert entry['source'] == MODEL['source'] and entry['reduced'] == MODEL['reduced']
    assert set(MODEL['reduced']) == {'num_hidden_layers', 'n_routed_experts', 'vocab_size'}
    assert (MODEL['num_hidden_layers'], MODEL['n_routed_experts'],
            MODEL['num_routed_experts'], MODEL['first_local_expert'],
            MODEL['vocab_size']) == (24, 32, 128, 0, 32064)
    assert MODEL['published'] == {
        'num_hidden_layers': 48, 'n_routed_experts': 128, 'vocab_size': 128256,
    }
    assert set(MODEL['held']) == {'layers', 'experts', 'vocabulary'}
    assert len(MODEL['assumed']) >= 4 and 'e_score_correction_bias' in MODEL['assumed'][1]
    assert '2 pipeline stages' in MODEL['deployment'] and '4 chips' in MODEL['deployment']
    engine = MODEL['engine']
    assert (engine['max_model_len'], engine['block_size'], engine['decode_steps'],
            engine['prefill_chunk_tokens'], engine['prefill_min_bucket']) == (8448, 16, 8, 512, 512)
    assert set(MODEL['engine_notes']) == set(engine)
    assert MODEL['expect_attn_backend'] == 'pallas'
    cell = manifest.cell(CELL)
    assert cell['chips'] == 1 and cell['config'] == 'kanana-2-30b-a3b'
    assert len(cell['why']) <= 200 and 'over its share' in cell['why']
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'deepseek_v3_closed'
    # The laguna cell's traffic to the letter.
    laguna = manifest.load('workloads', LAGUNA_CELL)
    for key in ('loop', 'sampling', 'traffic', 'warmup', 'trace'):
        assert workload[key] == laguna[key], key
    assert {m['name'] for m in manifest.metrics_of('end_to_end', CELL)} == {
        'gen_tok_s', 'setup_s',
    }
    assert {m['name'] for m in manifest.metrics_of('per_layer', CELL)} == {
        'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
        'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
        'model.moe_time_share.batch', 'model.moe_held_pair_share.batch',
        'model.deepseek_decode_step_ms.batch', 'model.deepseek_decode_bw_share.batch',
        'kernel.latent_attn_time_share.batch', 'kernel.latent_proj_time_share.batch',
        'kernel.latent_attn_roofline_share.batch',
    }
    for m in manifest.data['per_layer']:
        if 'deepseek' in m['name'] or 'latent' in m['name']:
            assert m['workloads'] == [CELL] and m['moves'] == 'gen_tok_s'
    # The kernel's pattern names the decode window's result types.
    spec = manifest.load('metrics', 'kernel.latent_attn_roofline_share.batch')
    assert f"bf16\\[{engine['max_num_seqs']},1,32,512\\]" in spec['args']['pattern']
    # The two scope patterns tell the scopes apart.
    import re

    attn = manifest.load('metrics', 'kernel.latent_attn_time_share.batch')['args']['pattern']
    proj = manifest.load('metrics', 'kernel.latent_proj_time_share.batch')['args']['pattern']
    assert re.search(attn, 'distllm.attn_latent') and not re.search(attn, 'distllm.attn_latent_proj')
    assert re.search(proj, 'distllm.attn_latent_proj') and not re.search(proj, 'distllm.attn_latent')


def _ctx(capture=None):
    return SimpleNamespace(config=MODEL, device_kind='TPU v5e', capture=capture)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the records or the programs (the parent commit),
    or a run without a traced slice, leaves the metric out and raises
    nothing."""
    obs = {'flight': [{'kind': 'decode', 'batch': 4, 'tokens': 32}],
           'counters': {}, 'trace': None}
    assert deepseek_v3.decode_bw_share(_ctx(), obs, '^jit_deepseek_window_fn') is None
    assert deepseek_v3.latent_attn_roofline_share(_ctx(), obs, 'custom-call') is None
    traced = dict(obs, trace={'busy_s': 1.0, 'op_s': {}, 'module_s': {
        'jit_window_fn(1)': 1.0}, 'module_n': {'jit_window_fn(1)': 3}})
    assert deepseek_v3.decode_bw_share(_ctx(), traced, '^jit_deepseek_window_fn') is None
    assert deepseek_v3.latent_attn_roofline_share(_ctx(), traced, 'custom-call') is None


def test_readers_price_the_stored_rows_once():
    windows = [
        {'kind': 'decode', 'batch': 48, 'tokens': 384, 't0_s': 10.0 + i,
         'kv_blocks': 9000}
        for i in range(3)
    ]
    capture = SimpleNamespace(t_start=10.5, t_stop=12.5)  # holds two of them
    kernel = '%distllm.attn_latent.47 custom-call bf16[48,1,32,512]{3,2,1,0}'
    obs = {
        'flight': windows, 'counters': {},
        'trace': {
            'busy_s': 4.0, 'module_s': {'jit_deepseek_window_fn(1)': 1.2},
            'module_n': {'jit_deepseek_window_fn(1)': 3},
            'op_s': {kernel: 0.9,
                     '%distllm.attn_latent.9 custom-call bf16[4,1,16384,512]{3,2,1,0}': 1.0},
        },
    }
    # 1.2 s over 3 runs x 8 steps = 50 ms a step.
    bytes_moved = deepseek_v3_bytes.decode_step_bytes(MODEL, 16 * 9000)
    share = deepseek_v3.decode_bw_share(_ctx(), obs, '^jit_deepseek_window_fn')
    assert share == pytest.approx(100 * bytes_moved / 819e9 / 0.05) and share < 100
    pattern = Manifest(ROOT / 'BENCHMARK.json').load(
        'metrics', 'kernel.latent_attn_roofline_share.batch')['args']['pattern']
    tokens = 2 * 8 * 16 * 9000  # two windows inside the slice x steps x tokens
    least = max(tokens * 24 * 1280 / 819e9, tokens * 24 * 69632 / 197e12)
    assert least == tokens * 24 * 1280 / 819e9  # the bytes are what bind
    got = deepseek_v3.latent_attn_roofline_share(_ctx(capture), obs, pattern)
    assert got == pytest.approx(100 * least / 0.9) and got < 100


def _rehearse(trace_flag: int, tree: Path = ROOT) -> subprocess.CompletedProcess:
    manifest = tree / 'benchmarks/tests/rehearsal_deepseek_v3/BENCHMARK.json'
    return subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-kanana.batch_mixed_lengths', '--seed', '3200000023', '--seconds', '1',
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
    assert detail['token_gap_mean_std'] < 1e-3
    assert detail['kv_content_error'] < 1e-5 and len(detail['kv_content_error_by_row']) == 6
    pool = detail['kv_pools']['latent']
    assert pool['block_shape'] == [4, 256]
    assert pool['bytes'] == pool['blocks'] * 4 * 256 * 4 * pool['layers']
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
            'engine.compiles_in_window.batch', 'model.moe_held_pair_share.batch'} <= set(traced)
    assert 30 < traced['model.moe_held_pair_share.batch']['value'] < 70


def test_the_cell_fails_at_once_without_the_model_module(tmp_path):
    """On a checkout that lacks ``models/deepseek_v3.py`` (the parent
    commit) the driver's first import fails: exit code non-zero, nothing
    allocated, no result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(ROOT / 'distllm_tpu', tree / 'distllm_tpu',
                    ignore=shutil.ignore_patterns('deepseek_v3.py', '__pycache__', '_build', '*.so'))
    init = tree / 'distllm_tpu/models/__init__.py'
    init.write_text('')  # the parent's table has no such row either
    done = _rehearse(0, tree)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'deepseek_v3' in done.stderr
