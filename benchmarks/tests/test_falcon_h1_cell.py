"""The cell PR 41 adds (``falcon-h1-34b.batch_generate``): its byte and
operation account against the issue's arithmetic and the program's own
parameter tree, its files against what the issue states, its readers on
hand-made records, and a CPU rehearsal through the harness at toy sizes
(``rehearsal_falcon_h1``; never a measurement)."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import falcon_h1_bytes
from benchmarks.manifest import Manifest
from benchmarks.readers import falcon_h1

ROOT = Path(__file__).resolve().parents[2]
CELL = 'falcon-h1-34b.batch_generate'
GRANITE_CELL = 'granite-4.0-h-small.batch_generate'
MODEL = json.loads((ROOT / 'benchmarks/configs/falcon-h1-34b.json').read_text())
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
OWN = [
    'model.falcon_h1_decode_step_ms.batch', 'model.falcon_h1_decode_bw_share.batch',
    'model.ssm_decode_bw_share.batch', 'kernel.paged_attn_roofline_share.falcon_h1',
    'model.head_sample_time_share.batch',
]


def test_byte_account_matches_the_issues_arithmetic_and_the_programs_tree():
    import jax

    from distllm_tpu.models import falcon_h1 as program

    assert falcon_h1_bytes.layer_params(MODEL) == 430_120_032
    assert falcon_h1_bytes.mixer_params(MODEL) == pytest.approx(68.35e6, rel=1e-3)
    held = falcon_h1_bytes.held_params(MODEL)
    assert held == 6 * 430_120_032 + 2 * 1_336_934_400 + 5_120 == 5_254_594_112
    cfg = program.FalconH1Config.from_hf_config(MODEL)
    shapes = jax.eval_shape(
        lambda: program.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == held
    assert held * 2 == pytest.approx(10.51e9, rel=1e-3)
    # A step reads the layers and the head, not the embedding.
    assert falcon_h1_bytes.weight_params(MODEL) == held - 1_336_934_400
    # The published model: 72 such layers are the 33.64 B.
    whole = falcon_h1_bytes.held_params({**MODEL, 'num_hidden_layers': 72})
    assert whole == pytest.approx(33.64e9, rel=1e-3)
    # A token's pages: 6 layers x (K + V) x 4 x 128 x 2 bytes = 12 KiB.
    assert falcon_h1_bytes.kv_bytes_per_token(MODEL) == 12 * 1024
    # A sequence's state: 6 x (4,194,304 + 30,720) bytes, what the program's
    # own state_spec holds.
    state = falcon_h1_bytes.state_bytes_per_sequence(MODEL)
    assert state == 6 * (4_194_304 + 30_720) == 25_350_144
    spec = cfg.model_copy(update={'dtype': 'bfloat16'}).state_spec()
    assert state == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(spec)
    )
    # 2 x 20 heads x (128 + 128) operations a cached token a layer.
    assert falcon_h1_bytes.attn_flops(MODEL, 1) == 6 * 2 * 20 * 256
    # 96 rows at 60 k cached tokens (the issue's sizing without the sampler):
    # 7.84 GB of layers and head, 0.74 GB of pages, 4.87 GB of state.
    step = falcon_h1_bytes.decode_step_bytes(MODEL, 96, 60_000)
    assert step == pytest.approx(13.44e9, rel=0.01)
    update = falcon_h1_bytes.state_update_bytes(MODEL, 96, 1)
    assert update == pytest.approx(4.867e9 + 2 * 6 * 68.35e6, rel=1e-3)
    # The pools the configuration asks for, as the engine reports them.
    engine = MODEL['engine']
    assert falcon_h1_bytes.kv_bytes(MODEL, engine['num_blocks'] * 16) == 1_610_612_736
    assert engine['max_num_seqs'] * state == 2_433_613_824


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    assert len(manifest.data['workloads']) == 7
    assert all(c['chips'] == 1 for c in manifest.data['workloads'])
    entry = manifest.data['configs'][-1]
    assert entry['name'] == 'falcon-h1-34b' and len(entry['why']) <= 200
    assert entry['source'] == MODEL['source'] == (
        'https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json'
    )
    assert entry['reduced'] == MODEL['reduced'] == ['num_hidden_layers']
    assert MODEL['num_hidden_layers'] == 6
    assert MODEL['published']['num_hidden_layers'] == 72
    if CATALOG.exists():  # every published key but the reduced one, unchanged
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if '"Falcon-H1-34B-Instruct"' in line
        )
        assert entry['source'] == row['source_url']
        for key, value in row['config'].items():
            if key not in MODEL['reduced']:
                assert MODEL[key] == value, key
    assert {'layers', 'vocabulary', 'parameters', 'a_sequence'} == set(MODEL['held'])
    assert '12 stages of 6 whole layers' in MODEL['deployment']
    assert 'BOTH ends of the vocabulary' in MODEL['deployment']
    assert 'twelve times' in MODEL['deployment']
    assumed = ' '.join(MODEL['assumed'])
    for word in ('ssm_multipliers', 'group', 'rotate', 'final_layernorm',
                 'tokenizer', 'scale'):
        assert word in assumed, word
    engine = MODEL['engine']
    assert engine == {
        'max_num_seqs': 96, 'num_blocks': 8192, 'block_size': 16,
        'prefill_chunk_tokens': 512, 'prefill_min_bucket': 128,
        'max_model_len': 4096, 'enable_prefix_cache': False,
        'attn_backend': 'auto', 'decode_steps': 8,
    }
    assert set(MODEL['engine_notes']) == set(engine)
    assert MODEL['expect_attn_backend'] == 'pallas' and MODEL['dtype'] == 'bfloat16'
    cell = manifest.cell(CELL)
    assert cell == manifest.data['workloads'][-1]
    assert cell['chips'] == 1 and cell['config'] == 'falcon-h1-34b'
    assert len(cell['why']) <= 200 and '6 of 72 layers' in cell['why']
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'falcon_h1_closed'
    # The granite cell's traffic to the letter: the two Mamba-2 models are
    # read under one load.
    granite = manifest.load('workloads', GRANITE_CELL)
    for key in ('loop', 'sampling', 'warmup', 'trace'):
        assert workload[key] == granite[key], key
    for key in ('schedule_seed', 'prompts_per_call', 'prompt_tokens', 'output_tokens'):
        assert workload['traffic'][key] == granite['traffic'][key], key
    assert workload['traffic']['prompts_per_call'] == 2 * engine['max_num_seqs']
    assert {m['name'] for m in manifest.metrics_of('end_to_end', CELL)} == {
        'gen_tok_s', 'setup_s',
    }
    assert {m['name'] for m in manifest.metrics_of('per_layer', CELL)} == set(OWN) | {
        'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
        'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
        'model.ssm_time_share.batch', 'kernel.full_attn_time_share.batch',
    }
    assert [m['name'] for m in manifest.data['per_layer'][-5:]] == OWN
    for m in manifest.data['per_layer'][-5:]:
        assert m['workloads'] == [CELL] and m['moves'] == 'gen_tok_s'
        assert m['source'] == 'device_trace'
    # Every list the cell joined has it last: appended, nothing moved.
    for group in ('end_to_end', 'per_layer'):
        for m in manifest.data[group]:
            if CELL in m.get('workloads', []):
                assert m['workloads'][-1] == CELL
    # The kernel's pattern names a program and a scope, no result type; the
    # head's finds the two scopes and no other.
    kernel = manifest.load(
        'metrics', 'kernel.paged_attn_roofline_share.falcon_h1')['args']['pattern']
    assert re.search(kernel, 'jit_falcon_h1_window_fn(1234) distllm.attn_full')
    assert not re.search(kernel, 'jit_falcon_h1_prefill_fn(1234) distllm.attn_full')
    assert not re.search(kernel, 'jit_falcon_h1_window_fn(1234) distllm.ssm_decode')
    assert 'bf16' not in kernel
    head = manifest.load('metrics', 'model.head_sample_time_share.batch')['args']['pattern']
    assert re.search(head, 'distllm.head') and re.search(head, 'distllm.sample')
    assert not re.search(head, 'distllm.attn_full')
    ssm = manifest.load('metrics', 'model.ssm_decode_bw_share.batch')['args']['pattern']
    assert re.search(ssm, 'distllm.ssm_decode') and not re.search(ssm, 'distllm.ssm_prefill')


def _ctx(capture=None):
    return SimpleNamespace(config=MODEL, device_kind='TPU v5e', capture=capture)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the counter, the programs or the scopes (the
    parent commit), or a run without a traced slice, leaves the metric out
    and raises nothing."""
    obs = {'flight': [{'kind': 'decode', 'batch': 4, 'tokens': 32, 'kv_blocks': 9}],
           'counters': {}, 'trace': None}
    assert falcon_h1.decode_bw_share(_ctx(), obs, '^jit_falcon_h1_window_fn') is None
    assert falcon_h1.ssm_decode_bw_share(_ctx(), obs, 'ssm_decode') is None
    assert falcon_h1.paged_attn_roofline_share(_ctx(), obs, 'attn_full') is None
    traced = dict(
        obs, kernel_call_s={}, scope_s={'distllm.ssm_decode': 1.0},
        trace={'busy_s': 1.0, 'op_s': {}, 'module_s': {'jit_hybrid_window_fn(1)': 1.0},
               'module_n': {'jit_hybrid_window_fn(1)': 3}},
    )
    capture = SimpleNamespace(t_start=0.0, t_stop=1e9)
    assert falcon_h1.decode_bw_share(_ctx(), traced, '^jit_falcon_h1_window_fn') is None
    # a decode record without ``state_rows``: another family's, or the parent's
    assert falcon_h1.ssm_decode_bw_share(_ctx(capture), traced, 'ssm_decode') is None
    assert falcon_h1.paged_attn_roofline_share(_ctx(capture), traced, 'attn_full') is None


def test_readers_count_a_state_once_read_and_once_written():
    windows = [
        {'kind': 'decode', 'batch': 96, 'tokens': 768, 't0_s': 10.0 + i,
         'kv_blocks': 3600, 'state_rows': 8 * 90}
        for i in range(3)
    ]
    capture = SimpleNamespace(t_start=10.5, t_stop=12.5)  # holds two of them
    obs = {
        'flight': windows, 'counters': {},
        'trace': {
            'busy_s': 4.0, 'module_s': {'jit_falcon_h1_window_fn(1)': 0.6},
            'module_n': {'jit_falcon_h1_window_fn(1)': 3}, 'op_s': {},
        },
        'scope_s': {'distllm.ssm_decode': 0.2, 'distllm.ssm_prefill': 1.0},
        'kernel_call_s': {
            'jit_falcon_h1_window_fn(1) distllm.attn_full': 0.05,
            'jit_falcon_h1_prefill_fn(2) distllm.attn_full': 1.0,
        },
    }
    # 0.6 s over 3 runs x 8 steps = 25 ms a step; 90 rows a step on average.
    bytes_moved = falcon_h1_bytes.decode_step_bytes(MODEL, 90, 16 * 3600)
    share = falcon_h1.decode_bw_share(_ctx(), obs, '^jit_falcon_h1_window_fn')
    assert share == pytest.approx(100 * bytes_moved / 819e9 / 0.025) and share < 100
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    pattern = manifest.load('metrics', 'model.ssm_decode_bw_share.batch')['args']['pattern']
    # two windows inside the slice: 2 x 720 (row, step) pairs over 16 steps
    least = falcon_h1_bytes.state_update_bytes(MODEL, 2 * 720, 16) / 819e9
    assert least == pytest.approx(
        (2 * 25_350_144 * 1440 + 2 * 6 * falcon_h1_bytes.mixer_params(MODEL) * 16) / 819e9
    )
    got = falcon_h1.ssm_decode_bw_share(_ctx(capture), obs, pattern)
    assert got == pytest.approx(100 * least / 0.2) and got < 100
    pattern = manifest.load(
        'metrics', 'kernel.paged_attn_roofline_share.falcon_h1')['args']['pattern']
    tokens = 2 * 8 * 16 * 3600  # two windows x steps x tokens
    least = max(tokens * 12288 / 819e9, tokens * 6 * 2 * 20 * 256 / 197e12)
    assert least == tokens * 12288 / 819e9  # the bytes are what bind
    got = falcon_h1.paged_attn_roofline_share(_ctx(capture), obs, pattern)
    assert got == pytest.approx(100 * least / 0.05) and got < 100


def _rehearse(trace_flag: int, tree: Path = ROOT) -> subprocess.CompletedProcess:
    manifest = tree / 'benchmarks/tests/rehearsal_falcon_h1/BENCHMARK.json'
    return subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-falcon-h1.batch_generate', '--seed', '3200000023', '--seconds', '1',
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
    assert detail['compiles_in_window'] == 0
    # 6 slots: the 6 first holders, then 2 second holders; float32 on both sides
    assert len(detail['token_gap_max_by_row']) == 8
    assert detail['token_gap_mean_std'] <= detail['token_gap_max_std'] < 1e-3
    assert detail['ssm_state_error'] < 1e-5 and detail['conv_state_error'] < 1e-5
    assert detail['kv_content_error'] <= detail['kv_content_error_max_row'] < 1e-5
    pool = detail['kv_pools']['kv']
    assert pool['block_shape'] == [4, 8] and pool['layers'] == 3  # every layer
    assert pool['bytes'] == pool['blocks'] * 4 * 8 * 4 * 2 * pool['layers']
    assert detail['state_pool'] == {
        'slots': 6, 'bytes': 6 * 3 * (3 * 88 + 4 * 6 * 16) * 4,
        'bytes_per_slot': 3 * (3 * 88 + 4 * 6 * 16) * 4,
        'leaves': [  # two kinds of leaf, one of each a layer
            {'count': 3, 'shape': [3, 88], 'dtype': 'float32'},
            {'count': 3, 'shape': [4, 6, 16], 'dtype': 'float32'},
        ],
    }
    calls = detail['calls']
    assert calls and all(c['steps_tokens'] == calls[0]['steps_tokens'] for c in calls)
    done = _rehearse(1)
    assert done.returncode == 0, done.stderr[-2000:]
    traced = json.loads(done.stdout.strip().splitlines()[-1])['rehearsal_metrics']
    # What needs no device trace reads on the CPU too.
    assert {'engine.decode_occupancy.batch', 'engine.window_host_ms.batch',
            'engine.compiles_in_window.batch'} <= set(traced)


def test_the_cell_fails_at_once_without_the_model_module(tmp_path):
    """On a checkout that lacks ``models/falcon_h1.py`` (the parent commit,
    with this PR's benchmark files laid over it) the driver's first import
    fails: exit code non-zero, nothing allocated, no result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(ROOT / 'distllm_tpu', tree / 'distllm_tpu',
                    ignore=shutil.ignore_patterns('falcon_h1.py', '__pycache__', '_build', '*.so'))
    init = tree / 'distllm_tpu/models/__init__.py'
    init.write_text('')  # the parent's table has no such row either
    done = _rehearse(0, tree)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'falcon_h1' in done.stderr
