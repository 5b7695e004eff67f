"""The cell PR 39 adds (``lfm2-8b-a1b.batch_mixed_lengths_wide``): its byte
and operation account against the issue's arithmetic and the program's own
parameter tree, its files against what the issue states, its readers on
hand-made records and a hand-made trace, and a CPU rehearsal through the
harness at toy sizes (``rehearsal_lfm2``; never a measurement)."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import lfm2_bytes
from benchmarks.manifest import Manifest
from benchmarks.readers import lfm2

ROOT = Path(__file__).resolve().parents[2]
CELL = 'lfm2-8b-a1b.batch_mixed_lengths_wide'
KANANA_CELL = 'kanana-2-30b-a3b.batch_mixed_lengths'
MODEL = json.loads((ROOT / 'benchmarks/configs/lfm2-8b-a1b.json').read_text())
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')


def test_byte_account_matches_the_issues_arithmetic_and_the_programs_tree():
    import jax

    from distllm_tpu.models import lfm2 as program

    params = lfm2_bytes.weight_params(MODEL)
    assert params == pytest.approx(4.465e9, rel=1e-3)  # 4,465 M held
    cfg = program.Lfm2MoeConfig.from_hf_config(MODEL)
    shapes = jax.eval_shape(
        lambda: program.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == params
    assert params * 2 == pytest.approx(8.93e9, rel=1e-3)
    # The published model: 32 experts held would be the 8.34 B.
    whole = lfm2_bytes.weight_params({**MODEL, 'num_experts': 32})
    assert whole == pytest.approx(8.34e9, rel=2e-3)
    # A token's pages: 6 layers x (K + V) x 8 x 64 x 2 bytes = 12 KiB.
    assert lfm2_bytes.kv_bytes_per_token(MODEL) == 12 * 1024
    assert lfm2_bytes.layer_counts(MODEL) == {
        'conv': 18, 'attn': 6, 'dense': 2, 'sparse': 22,
    }
    # A sequence's state: 18 x 2 x 2048 x 2 bytes = 144 KiB.
    assert lfm2_bytes.state_bytes_per_sequence(MODEL) == 144 * 1024
    # 2 x 32 heads x (64 + 64) operations a cached token a layer.
    assert lfm2_bytes.attn_flops(MODEL, 1) == 6 * 2 * 32 * 128
    # 128 rows at 371 k tokens (the issue's sizing): 8.9 GB of weights, 4.6 GB
    # of pages, 38 MB of state moved: 13.5 GB, 16.5 ms at 819 GB/s.
    step = lfm2_bytes.decode_step_bytes(MODEL, 128, 371_000)
    assert step == pytest.approx(13.5e9, rel=0.01)
    # The cell's 96 rows at 278 k tokens: 8.9 + 3.4 GB.
    step = lfm2_bytes.decode_step_bytes(MODEL, 96, 278_000)
    assert step == pytest.approx(12.37e9, rel=0.01)
    # The pool the configuration asks for, as the engine reports it.
    blocks = MODEL['engine']['num_blocks']
    assert lfm2_bytes.kv_bytes(MODEL, blocks * 16) == blocks * 196608
    assert blocks * 196608 == pytest.approx(3.775e9, rel=1e-3)


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    assert len(manifest.data['workloads']) == 6
    assert all(c['chips'] == 1 for c in manifest.data['workloads'])
    entry = manifest.data['configs'][-1]
    assert entry['name'] == 'lfm2-8b-a1b'
    assert entry['source'] == MODEL['source'] == (
        'https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json'
    )
    assert entry['reduced'] == MODEL['reduced'] == ['num_experts']
    assert (MODEL['num_experts'], MODEL['num_routed_experts'],
            MODEL['first_local_expert']) == (16, 32, 0)
    assert MODEL['published']['num_experts'] == 32
    if CATALOG.exists():  # every published key but the reduced one, unchanged
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if '"LFM2-8B-A1B"' in line
        )
        assert entry['source'] == row['source_url']
        for key, value in row['config'].items():
            if key not in MODEL['reduced']:
                assert MODEL[key] == value, key
    assert {'layers', 'experts', 'vocabulary', 'parameters'} == set(MODEL['held'])
    assert len(MODEL['assumed']) == 9 and 'expert_bias' in MODEL['assumed'][5]
    assert 'two chips' in MODEL['deployment'] and 'expert mesh axis' in MODEL['deployment']
    engine = MODEL['engine']
    assert engine == {
        'max_num_seqs': 96, 'num_blocks': 19200, 'block_size': 16,
        'prefill_chunk_tokens': 512, 'prefill_min_bucket': 512,
        'max_model_len': 8448, 'enable_prefix_cache': False,
        'attn_backend': 'auto', 'decode_steps': 8,
    }
    assert set(MODEL['engine_notes']) == set(engine)
    assert MODEL['expect_attn_backend'] == 'pallas' and MODEL['dtype'] == 'bfloat16'
    cell = manifest.cell(CELL)
    assert cell == manifest.data['workloads'][-1]
    assert cell['chips'] == 1 and cell['config'] == 'lfm2-8b-a1b'
    assert len(cell['why']) <= 200 and 'whole on each expert chip' in cell['why']
    assert '377-386 s' in cell['why']  # what refused the issue's 128 rows
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'lfm2_closed'
    # The laguna and kanana cells' traffic with 96 rows for 48 (the issue's
    # second size).
    kanana = manifest.load('workloads', KANANA_CELL)
    for key in ('loop', 'sampling', 'warmup', 'trace'):
        assert workload[key] == kanana[key], key
    assert workload['traffic'] == {**kanana['traffic'], 'prompts_per_call': 96}
    assert workload['traffic']['prompts_per_call'] == engine['max_num_seqs']
    assert {m['name'] for m in manifest.metrics_of('end_to_end', CELL)} == {
        'gen_tok_s', 'setup_s',
    }
    own = {
        'model.lfm2_decode_step_ms.batch', 'model.lfm2_decode_bw_share.batch',
        'model.conv_time_share.batch', 'kernel.paged_attn_roofline_share.lfm2',
    }
    assert {m['name'] for m in manifest.metrics_of('per_layer', CELL)} == own | {
        'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
        'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
        'model.moe_time_share.batch', 'model.moe_held_pair_share.batch',
        'kernel.full_attn_time_share.batch',
    }
    assert [m['name'] for m in manifest.data['per_layer'][-4:]] == [
        'model.lfm2_decode_step_ms.batch', 'model.lfm2_decode_bw_share.batch',
        'model.conv_time_share.batch', 'kernel.paged_attn_roofline_share.lfm2',
    ]
    for m in manifest.data['per_layer'][-4:]:
        assert m['workloads'] == [CELL] and m['moves'] == 'gen_tok_s'
        assert m['source'] == 'device_trace'
    # Every list the cell joined has it last: appended, nothing moved.
    for group in ('end_to_end', 'per_layer'):
        for m in manifest.data[group]:
            if CELL in m.get('workloads', []):
                assert m['workloads'][-1] == CELL
    # The conv pattern finds both scopes and no other; the kernel's names a
    # program and a scope, no result type.
    conv = manifest.load('metrics', 'model.conv_time_share.batch')['args']['pattern']
    assert re.search(conv, 'distllm.conv_prefill') and re.search(conv, 'distllm.conv_decode')
    assert not re.search(conv, 'distllm.attn_full') and not re.search(conv, 'distllm.moe')
    kernel = manifest.load(
        'metrics', 'kernel.paged_attn_roofline_share.lfm2')['args']['pattern']
    assert re.search(kernel, 'jit_lfm2_window_fn(1234) distllm.attn_full')
    assert not re.search(kernel, 'jit_lfm2_prefill_fn(1234) distllm.attn_full')
    assert not re.search(kernel, 'jit_lfm2_window_fn(1234) distllm.moe')
    assert 'bf16' not in kernel


def _ctx(capture=None):
    return SimpleNamespace(config=MODEL, device_kind='TPU v5e', capture=capture)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the records or the programs (the parent commit),
    or a run without a traced slice, leaves the metric out and raises
    nothing."""
    obs = {'flight': [{'kind': 'decode', 'batch': 4, 'tokens': 32}],
           'counters': {}, 'trace': None}
    assert lfm2.decode_bw_share(_ctx(), obs, '^jit_lfm2_window_fn') is None
    assert lfm2.paged_attn_roofline_share(_ctx(), obs, 'attn_full') is None
    traced = dict(obs, kernel_call_s={}, trace={'busy_s': 1.0, 'op_s': {}, 'module_s': {
        'jit_window_fn(1)': 1.0}, 'module_n': {'jit_window_fn(1)': 3}})
    assert lfm2.decode_bw_share(_ctx(), traced, '^jit_lfm2_window_fn') is None
    assert lfm2.paged_attn_roofline_share(_ctx(), traced, 'attn_full') is None
    assert lfm2.load_xspace(None) is None


def test_readers_price_a_page_once_for_k_and_once_for_v():
    windows = [
        {'kind': 'decode', 'batch': 128, 'tokens': 1024, 't0_s': 10.0 + i,
         'kv_blocks': 23000}
        for i in range(3)
    ]
    capture = SimpleNamespace(t_start=10.5, t_stop=12.5)  # holds two of them
    obs = {
        'flight': windows, 'counters': {},
        'trace': {
            'busy_s': 4.0, 'module_s': {'jit_lfm2_window_fn(1)': 0.6},
            'module_n': {'jit_lfm2_window_fn(1)': 3}, 'op_s': {},
        },
        'kernel_call_s': {
            'jit_lfm2_window_fn(1) distllm.attn_full': 0.2,
            'jit_lfm2_prefill_fn(2) distllm.attn_full': 1.0,
            'jit_lfm2_window_fn(1) distllm.moe': 0.3,
        },
    }
    # 0.6 s over 3 runs x 8 steps = 25 ms a step.
    bytes_moved = lfm2_bytes.decode_step_bytes(MODEL, 128, 16 * 23000)
    share = lfm2.decode_bw_share(_ctx(), obs, '^jit_lfm2_window_fn')
    assert share == pytest.approx(100 * bytes_moved / 819e9 / 0.025) and share < 100
    pattern = Manifest(ROOT / 'BENCHMARK.json').load(
        'metrics', 'kernel.paged_attn_roofline_share.lfm2')['args']['pattern']
    tokens = 2 * 8 * 16 * 23000  # two windows inside the slice x steps x tokens
    least = max(tokens * 12288 / 819e9, tokens * 49152 / 197e12)
    assert least == tokens * 12288 / 819e9  # the bytes are what bind
    got = lfm2.paged_attn_roofline_share(_ctx(capture), obs, pattern)
    assert got == pytest.approx(100 * least / 0.2) and got < 100


def test_kernel_seconds_are_by_program_and_scope():
    """The reduction from the profiler's protobuf to seconds of kernel
    calls, on a hand-made ``XSpace``: a call belongs to the program it
    starts in and to the scope its metadata names; other ops and calls
    outside every program are left out."""
    xplane_pb2 = pytest.importorskip('tensorflow.tsl.profiler.protobuf.xplane_pb2')
    space = xplane_pb2.XSpace()
    plane = space.planes.add()
    plane.name = '/device:TPU:0'
    plane.stat_metadata[1].name = 'tf_op'

    def metadata(key, name, text=None):
        entry = plane.event_metadata[key]
        entry.id, entry.name = key, name
        if text is not None:
            stat = entry.stats.add()
            stat.metadata_id, stat.str_value = 1, text

    metadata(1, '%distllm.attn_full.3 = bf16[128,4,8,128] custom-call',
             'jit(lfm2_window_fn)/while/body/distllm.attn_full/pallas_call:')
    metadata(2, '%fusion.1 = fusion', 'jit(f)/distllm.attn_full/mul:')
    metadata(3, '%ragged-dot-none.2 = custom-call', 'ragged-dot-none:')
    metadata(10, 'jit_lfm2_window_fn(77)')
    metadata(11, 'jit_lfm2_prefill_fn(78)')
    modules = plane.lines.add()
    modules.name = 'XLA Modules'
    for key, offset, duration in ((10, 0, 10_000_000), (11, 20_000_000, 10_000_000)):
        event = modules.events.add()
        event.metadata_id, event.offset_ps, event.duration_ps = key, offset, duration
    ops = plane.lines.add()
    ops.name = 'XLA Ops'
    for key, offset, duration in (
        (1, 1_000_000, 2_000_000), (2, 3_000_000, 1_000_000),
        (1, 5_000_000, 2_000_000), (3, 8_000_000, 1_000_000),
        (1, 21_000_000, 5_000_000), (1, 40_000_000, 1_000_000),
    ):
        event = ops.events.add()
        event.metadata_id, event.offset_ps, event.duration_ps = key, offset, duration
    assert lfm2.kernel_seconds(space) == pytest.approx({
        'jit_lfm2_window_fn(77) distllm.attn_full': 4e-6,
        'jit_lfm2_window_fn(77) distllm.moe': 1e-6,
        'jit_lfm2_prefill_fn(78) distllm.attn_full': 5e-6,
    })
    assert lfm2.kernel_seconds(xplane_pb2.XSpace()) is None


def _rehearse(trace_flag: int, tree: Path = ROOT) -> subprocess.CompletedProcess:
    manifest = tree / 'benchmarks/tests/rehearsal_lfm2/BENCHMARK.json'
    return subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-lfm2.batch_mixed_lengths_wide', '--seed', '3200000023', '--seconds', '1',
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
    assert detail['state_content_error'] < 1e-5 and detail['kv_content_error'] < 1e-5
    assert len(detail['state_content_error_by_row']) == 6
    pool = detail['kv_pools']['kv']
    assert pool['block_shape'] == [4, 32] and pool['layers'] == 2
    assert pool['bytes'] == pool['blocks'] * 4 * 32 * 4 * 2 * pool['layers']
    assert detail['state_pool'] == {
        'slots': 6, 'bytes': 6 * 4 * 2 * 64 * 4, 'bytes_per_slot': 4 * 2 * 64 * 4,
        'leaves': [{'count': 4, 'shape': [2, 64], 'dtype': 'float32'}],
    }
    # set-up is PR 36's account (``setup.*``), not a second one of the driver's
    assert 'setup_split_s' not in detail and 'window_engine' not in detail
    assert detail['kv_content_error'] <= detail['kv_content_error_max_row'] < 1e-5
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
    """On a checkout that lacks ``models/lfm2.py`` (the parent commit, with
    this PR's benchmark files laid over it) the driver's first import
    fails: exit code non-zero, nothing allocated, no result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(ROOT / 'distllm_tpu', tree / 'distllm_tpu',
                    ignore=shutil.ignore_patterns('lfm2.py', '__pycache__', '_build', '*.so'))
    init = tree / 'distllm_tpu/models/__init__.py'
    init.write_text('')  # the parent's table has no such row either
    done = _rehearse(0, tree)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'lfm2' in done.stderr
