"""The cell PR 45 adds (``solar-open2-250b.batch_long_documents``): its byte
and operation account against the issue's arithmetic and the program's own
parameter tree, its files against what the issue states, its readers on
hand-made records, and a CPU rehearsal through the harness at toy sizes
(``rehearsal_solar_open2``; never a measurement)."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import solar_open2_bytes
from benchmarks.manifest import Manifest
from benchmarks.readers import solar_open2

ROOT = Path(__file__).resolve().parents[2]
CELL = 'solar-open2-250b.batch_long_documents'
MODEL = json.loads((ROOT / 'benchmarks/configs/solar-open2-250b.json').read_text())
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
OWN = [
    'model.solar_open2_decode_step_ms.batch', 'model.solar_open2_decode_bw_share.batch',
    'model.kda_time_share.batch', 'kernel.kda_span_roofline_share.batch',
    'kernel.kda_step_bw_share.batch', 'kernel.paged_attn_roofline_share.solar_open2',
]
SHARED = [
    'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
    'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
    'model.moe_time_share.batch', 'model.moe_held_pair_share.batch',
    'kernel.full_attn_time_share.batch', 'model.head_sample_time_share.batch',
]


def test_byte_account_matches_the_issues_arithmetic_and_the_programs_tree():
    import jax

    from distllm_tpu.models import solar_open2 as program

    # The issue's table: 40 experts x 3 x 4096 x 1280 = 629 M a layer; a KDA
    # layer outside its routed experts 154.8 M (q, k, v, o 134.2 M; gates,
    # beta, conv 3.5 M; shared 15.7 M; router 1.3 M); the attention layer
    # 126.1 M; embedding and head 201 M; 3.3 G in all, 6.6 GB.
    assert 40 * solar_open2_bytes.expert_params(MODEL) == pytest.approx(629e6, rel=1e-3)
    outside = solar_open2_bytes.moe_params(MODEL, experts=0)
    assert outside == pytest.approx(15.7e6 + 1.3e6, rel=5e-3)
    kda = solar_open2_bytes.kda_mixer_params(MODEL)
    assert kda == pytest.approx(134.2e6 + 3.5e6, rel=1e-3)
    assert kda + outside == pytest.approx(154.8e6, rel=1e-3)
    gqa = solar_open2_bytes.gqa_mixer_params(MODEL)
    assert gqa == pytest.approx(100.7e6 + 8.4e6, rel=1e-3)
    assert gqa + outside == pytest.approx(126.1e6, rel=1e-3)
    held = solar_open2_bytes.held_params(MODEL)
    assert held == 3_308_377_920 and held * 2 == pytest.approx(6.6e9, rel=5e-3)
    cfg = program.SolarOpen2Config.from_hf_config(MODEL)
    shapes = jax.eval_shape(
        lambda: program.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == held
    # A step reads everything but the embedding's 24,576 rows.
    assert solar_open2_bytes.weight_params(MODEL) == held - 24576 * 4096
    # The published model: 250 B.
    whole = solar_open2_bytes.held_params({
        **MODEL, **{k: MODEL['published'][k] for k in MODEL['reduced']},
    })
    assert whole == pytest.approx(250.3e9, rel=1e-3)
    # A token's pages: one layer x (K + V) x 8 x 128 x 2 bytes = 4 KiB.
    assert solar_open2_bytes.kv_bytes_per_token(MODEL) == 4096
    # A sequence's state: 3 x (4,194,304 + 147,456) bytes, what the
    # program's own state_spec holds.
    state = solar_open2_bytes.state_bytes_per_sequence(MODEL)
    assert state == 3 * (64 * 128 * 128 * 4 + 3 * 24576 * 2) == 13_025_280
    spec = cfg.model_copy(update={'dtype': 'bfloat16'}).state_spec()
    assert state == sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(spec))
    # The recurrence: 7 operations a state element, a token, a layer; the
    # step's bytes the float32 state twice.
    assert solar_open2_bytes.kda_step_flops(MODEL, 1) == 7 * 64 * 128 * 128 * 3
    assert solar_open2_bytes.kda_span_flops(MODEL, 10) == 10 * 7 * 64 * 128 * 128 * 3
    assert solar_open2_bytes.kda_step_bytes(MODEL, 128) == 2 * 3 * 4_194_304 * 128
    assert solar_open2_bytes.kda_span_bytes(MODEL, 512, 1) == 3 * (
        5 * 64 * 128 * 2 * 512 + 2 * 4_194_304
    )
    # 2 x 64 heads x (128 + 128) operations a cached token.
    assert solar_open2_bytes.attn_flops(MODEL, 1) == 2 * 64 * 256
    # 128 rows at 742 k cached tokens (the issue's sizing): 6.4 GB of layers
    # and head, 3.0 GB of pages, 3.3 GB of state read and written.
    step = solar_open2_bytes.decode_step_bytes(MODEL, 128, 742_000)
    assert step == pytest.approx(6.42e9 + 3.04e9 + 3.33e9, rel=0.01)
    # The pools the configuration asks for, as the engine reports them.
    engine = MODEL['engine']
    assert solar_open2_bytes.kv_bytes(MODEL, engine['num_blocks'] * 16) == 3_221_225_472
    assert engine['max_num_seqs'] * state <= 128 * 13_025_280


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    assert all(c['chips'] == 1 for c in manifest.data['workloads'])
    entry = next(c for c in manifest.data['configs'] if c['name'] == 'solar-open2-250b')
    assert len(entry['why']) <= 200
    assert entry['source'] == MODEL['source'] == (
        'https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json'
    )
    assert entry['reduced'] == MODEL['reduced'] == [
        'num_hidden_layers', 'gqa_layers', 'n_routed_experts', 'vocab_size',
    ]
    assert (MODEL['num_hidden_layers'], MODEL['gqa_layers']) == (4, [0])
    assert (MODEL['n_routed_experts'], MODEL['num_routed_experts']) == (40, 320)
    assert MODEL['vocab_size'] == 24576 == 196608 // 8
    assert MODEL['published']['num_hidden_layers'] == 48
    assert MODEL['published']['n_routed_experts'] == 320
    assert MODEL['published']['vocab_size'] == 196608
    if CATALOG.exists():  # every published key but the reduced ones, unchanged
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if '"Solar-Open2-250B"' in line
        )
        assert entry['source'] == row['source_url']
        for key, value in row['config'].items():
            if key not in MODEL['reduced']:
                assert MODEL[key] == value, key
            else:
                assert MODEL['published'][key] == value, key
    assert {'layers', 'experts', 'vocabulary', 'parameters', 'a_sequence'} == set(MODEL['held'])
    assert '12 stages of one period' in MODEL['deployment']
    assert '96 v5e chips' in MODEL['deployment'] and 'one eighth' in MODEL['deployment']
    assumed = ' '.join(MODEL['assumed'])
    for word in ('kda_use_full_proj', 'low-rank', 'g_bias', 'use_gqa_gate',
                 'q/k norm', 'selection bias', 'A uniform', 'dt log-uniform',
                 'tokenizer', 'seeded'):
        assert word in assumed, word
    engine = MODEL['engine']
    assert {k: v for k, v in engine.items() if k != 'max_num_seqs'} == {
        'num_blocks': 49152, 'block_size': 16, 'prefill_chunk_tokens': 512,
        'prefill_min_bucket': 512, 'max_model_len': 16640,
        'enable_prefix_cache': False, 'attn_backend': 'auto', 'decode_steps': 8,
    }
    assert engine['max_num_seqs'] in (64, 96, 128)  # the ladder's rungs
    assert set(MODEL['engine_notes']) == set(engine)
    assert MODEL['expect_attn_backend'] == 'pallas' and MODEL['dtype'] == 'bfloat16'
    cell = manifest.cell(CELL)
    assert cell == manifest.data['workloads'][-1]
    assert cell['chips'] == 1 and cell['config'] == 'solar-open2-250b'
    assert len(cell['why']) <= 200 and '1/8 of a deployment' in cell['why']
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'solar_open2_closed'
    assert workload['sampling'] == {'temperature': 0.5, 'top_p': 0.95}
    traffic = workload['traffic']
    assert traffic['schedule_seed'] == 0 and traffic['prompts_per_call'] == 128
    assert traffic['prompt_tokens'] == {'dist': 'loguniform', 'lo': 1024, 'hi': 16384}
    assert traffic['output_tokens'] == {'dist': 'fixed', 'value': 256}
    assert workload['warmup'] == {'replica_calls': 1}
    assert workload['trace'] == {'delay_s': 0.0, 'seconds': 5.0}
    assert traffic['prompt_tokens']['hi'] + 256 == engine['max_model_len']
    assert {m['name'] for m in manifest.metrics_of('end_to_end', CELL)} == {
        'gen_tok_s', 'setup_s',
    }
    assert {m['name'] for m in manifest.metrics_of('per_layer', CELL)} == set(OWN + SHARED)
    assert [m['name'] for m in manifest.data['per_layer'][-6:]] == OWN
    for m in manifest.data['per_layer'][-6:]:
        assert m['workloads'] == [CELL] and m['moves'] == 'gen_tok_s'
        assert m['source'] == 'device_trace'
    # Every list the cell joined has it last: appended, nothing moved.
    for group in ('end_to_end', 'per_layer'):
        for m in manifest.data[group]:
            if CELL in m.get('workloads', []):
                assert m['workloads'][-1] == CELL
    # The kernel's pattern names a program and a scope, no result type; the
    # two forms of the rule are read under their own scopes.
    kernel = manifest.load(
        'metrics', 'kernel.paged_attn_roofline_share.solar_open2')['args']['pattern']
    assert re.search(kernel, 'jit_solar_open2_window_fn(1234) distllm.attn_full')
    assert not re.search(kernel, 'jit_solar_open2_prefill_fn(1234) distllm.attn_full')
    assert 'bf16' not in kernel
    span = manifest.load('metrics', 'kernel.kda_span_roofline_share.batch')['args']['pattern']
    step = manifest.load('metrics', 'kernel.kda_step_bw_share.batch')['args']['pattern']
    share = manifest.load('metrics', 'model.kda_time_share.batch')['args']['pattern']
    assert re.search(span, 'distllm.kda_span') and not re.search(span, 'distllm.kda_step')
    assert re.search(step, 'distllm.kda_step') and not re.search(step, 'distllm.kda_span')
    for scope in ('kda_proj', 'kda_span', 'kda_step', 'kda_out'):
        assert re.search(share, f'distllm.{scope}')
    assert not re.search(share, 'distllm.attn_full')


def _ctx(capture=None):
    return SimpleNamespace(config=MODEL, device_kind='TPU v5e', capture=capture)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the counters, the programs or the scopes (the
    parent commit), or a run without a traced slice, leaves the metric out
    and raises nothing."""
    obs = {'flight': [{'kind': 'decode', 'batch': 4, 'tokens': 32, 'kv_blocks': 9},
                      {'kind': 'prefill', 'batch': 4, 'tokens': 2048}],
           'counters': {}, 'trace': None}
    none = SimpleNamespace(t_start=None, t_stop=None)
    assert solar_open2.decode_bw_share(_ctx(none), obs, '^jit_solar_open2_window_fn') is None
    assert solar_open2.kda_span_roofline_share(_ctx(none), obs, 'kda_span') is None
    assert solar_open2.kda_step_bw_share(_ctx(none), obs, 'kda_step') is None
    assert solar_open2.paged_attn_roofline_share(_ctx(none), obs, 'attn_full') is None
    traced = dict(
        obs, kernel_call_s={}, scope_s={'distllm.ssm_decode': 1.0},
        trace={'busy_s': 1.0, 'op_s': {}, 'module_s': {'jit_hybrid_window_fn(1)': 1.0},
               'module_n': {'jit_hybrid_window_fn(1)': 3}},
    )
    capture = SimpleNamespace(t_start=0.0, t_stop=1e9)
    assert solar_open2.decode_bw_share(_ctx(capture), traced, '^jit_solar_open2_window_fn') is None
    # records without ``state_rows``: another family's, or the parent's; and
    # no scope of these names
    assert solar_open2.kda_span_roofline_share(_ctx(capture), traced, 'kda_span') is None
    assert solar_open2.kda_step_bw_share(_ctx(capture), traced, 'kda_step') is None
    assert solar_open2.paged_attn_roofline_share(_ctx(capture), traced, 'attn_full') is None


def test_readers_count_a_state_once_read_and_once_written():
    windows = [
        {'kind': 'decode', 'batch': 128, 'tokens': 1024, 't0_s': 10.0 + i,
         'kv_blocks': 40000, 'state_rows': 8 * 120}
        for i in range(3)
    ]
    spans = [
        {'kind': 'prefill', 'batch': 4, 'tokens': 2000, 'route': 'chunk',
         't0_s': 10.2 + i}
        for i in range(3)
    ]
    capture = SimpleNamespace(t_start=10.5, t_stop=12.5)  # holds two of each
    obs = {
        'flight': windows + spans, 'counters': {},
        'trace': {
            'busy_s': 4.0, 'module_s': {'jit_solar_open2_window_fn(1)': 0.6},
            'module_n': {'jit_solar_open2_window_fn(1)': 3}, 'op_s': {},
        },
        'scope_s': {'distllm.kda_step': 0.2, 'distllm.kda_span': 0.5,
                    'distllm.kda_proj': 1.0},
        'kernel_call_s': {
            'jit_solar_open2_window_fn(1) distllm.attn_full': 0.2,
            'jit_solar_open2_prefill_fn(2) distllm.attn_full': 1.0,
        },
    }
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    pattern = lambda name: manifest.load('metrics', name)['args']['pattern']  # noqa: E731
    # 0.6 s over 3 runs x 8 steps = 25 ms a step; 120 rows a step on average.
    bytes_moved = solar_open2_bytes.decode_step_bytes(MODEL, 120, 16 * 40000)
    share = solar_open2.decode_bw_share(_ctx(), obs, '^jit_solar_open2_window_fn')
    assert share == pytest.approx(100 * bytes_moved / 819e9 / 0.025) and share < 100
    # two windows inside the slice: 2 x 960 (row, step) pairs
    least = 2 * 3 * 4_194_304 * 2 * 960 / 819e9
    got = solar_open2.kda_step_bw_share(
        _ctx(capture), obs, pattern('kernel.kda_step_bw_share.batch'))
    assert got == pytest.approx(100 * least / 0.2) and got < 100
    # two dispatches inside the slice: 4000 counted tokens, 8 rows
    flops = 4000 * 7 * 64 * 128 * 128 * 3 / 197e12
    moved = 3 * (5 * 64 * 128 * 2 * 4000 + 2 * 4_194_304 * 8) / 819e9
    assert moved > flops  # the bytes are what bind
    got = solar_open2.kda_span_roofline_share(
        _ctx(capture), obs, pattern('kernel.kda_span_roofline_share.batch'))
    assert got == pytest.approx(100 * moved / 0.5) and got < 100
    tokens = 2 * 8 * 16 * 40000  # two windows x steps x tokens
    least = max(tokens * 4096 / 819e9, tokens * 2 * 64 * 256 / 197e12)
    assert least == tokens * 4096 / 819e9
    got = solar_open2.paged_attn_roofline_share(
        _ctx(capture), obs, pattern('kernel.paged_attn_roofline_share.solar_open2'))
    assert got == pytest.approx(100 * least / 0.2) and got < 100


def _rehearse(trace_flag: int, tree: Path = ROOT) -> subprocess.CompletedProcess:
    manifest = tree / 'benchmarks/tests/rehearsal_solar_open2/BENCHMARK.json'
    return subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-solar-open2.batch_long_documents', '--seed', '3200000023',
         '--seconds', '1', '--trace', str(trace_flag), '--allow-cpu',
         '--manifest', str(manifest)],
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
    # every KDA layer's slot, and the recurrence's forms from equal operands
    assert len(detail['kda_state_error']) == len(detail['kda_state_limit']) == 3
    assert max(detail['kda_state_error']) < 1e-5 > max(detail['conv_state_error'])
    assert detail['kda_equal_operand_error'] < 1e-5
    assert detail['kv_content_error'] <= detail['kv_content_error_max_row'] < 1e-5
    pool = detail['kv_pools']['kv']
    assert pool['block_shape'] == [4, 8] and pool['layers'] == 2  # the attention layers
    assert detail['state_pool'] == {
        'slots': 6, 'bytes': 6 * 3 * (3 * 72 + 3 * 8 * 8) * 4,
        'bytes_per_slot': 3 * (3 * 72 + 3 * 8 * 8) * 4,
        'leaves': [  # two kinds of leaf, one of each a KDA layer
            {'count': 3, 'shape': [3, 72], 'dtype': 'float32'},
            {'count': 3, 'shape': [3, 8, 8], 'dtype': 'float32'},
        ],
    }
    assert detail['moe_form']['decode(6)'] == 'dense'
    calls = detail['calls']
    assert calls and all(c['steps_tokens'] == calls[0]['steps_tokens'] for c in calls)
    done = _rehearse(1)
    assert done.returncode == 0, done.stderr[-2000:]
    traced = json.loads(done.stdout.strip().splitlines()[-1])['rehearsal_metrics']
    # What needs no device trace reads on the CPU too.
    assert {'engine.decode_occupancy.batch', 'engine.window_host_ms.batch',
            'engine.compiles_in_window.batch', 'model.moe_held_pair_share.batch'} <= set(traced)
    assert traced['model.moe_held_pair_share.batch']['value'] == pytest.approx(50.0, abs=15)


def test_the_cell_fails_at_once_without_the_model_module(tmp_path):
    """On a checkout that lacks ``models/solar_open2.py`` (the parent
    commit, with this PR's benchmark files laid over it) the driver's first
    import fails: exit code non-zero, nothing allocated, no result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(ROOT / 'distllm_tpu', tree / 'distllm_tpu',
                    ignore=shutil.ignore_patterns('solar_open2.py', 'kda.py', '__pycache__', '_build', '*.so'))
    init = tree / 'distllm_tpu/models/__init__.py'
    init.write_text('')  # the parent's table has no such row either
    done = _rehearse(0, tree)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'solar_open2' in done.stderr
