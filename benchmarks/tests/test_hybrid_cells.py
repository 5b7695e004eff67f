"""The cell PR 26 adds and the one it holds back: the byte account and the
readers of the hybrid cell, the prefix-hit reader of ``mistral7b.rag_sessions``
(files ready, not in the manifest: PERF.md section 7), the cells' files
against what the issue states, and a CPU rehearsal of each through the
harness at toy sizes (``rehearsal_hybrid``; never a measurement)."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import hybrid_bytes
from benchmarks.manifest import Manifest
from benchmarks.readers import hybrid

ROOT = Path(__file__).resolve().parents[2]
REHEARSAL = ROOT / 'benchmarks/tests/rehearsal_hybrid/BENCHMARK.json'
GRANITE = json.loads((ROOT / 'benchmarks/configs/granite-4.0-h-small.json').read_text())


def test_byte_account_matches_the_issues_arithmetic():
    # 4757 M parameters held, 38.2 MB of state a sequence, 4 KiB of KV a token.
    assert hybrid_bytes.weight_params(GRANITE) == pytest.approx(4757e6, rel=2e-3)
    assert hybrid_bytes.state_bytes_per_sequence(GRANITE) == 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2
    )
    assert hybrid_bytes.kv_bytes_per_token(GRANITE) == 4096
    step = hybrid_bytes.decode_step_bytes(GRANITE, rows=64, context_tokens=64 * 500)
    assert step == pytest.approx(14.5e9, rel=0.02)  # 17.7 ms at 819 GB/s
    # An emptier batch moves fewer bytes: only the rows that run count.
    assert hybrid_bytes.decode_step_bytes(GRANITE, 32, 32 * 500) < step


def test_configuration_keeps_published_widths_and_states_the_cut():
    catalog = ROOT.parent / 'opt/skills/guides/model-configs/architectures.jsonl'
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    entry = next(c for c in manifest.data['configs'] if c['name'] == 'granite-4.0-h-small')
    assert entry['source'] == GRANITE['source'] and entry['reduced'] == GRANITE['reduced']
    assert set(GRANITE['reduced']) == {
        'num_hidden_layers', 'layer_types', 'num_local_experts', 'vocab_size',
    }
    assert GRANITE['published'] == {
        'num_hidden_layers': 40, 'num_local_experts': 72, 'vocab_size': 100352,
        'layer_types': 'period 10 (M M M M M A M M M M) x 4',
    }
    widths = {
        'hidden_size': 4096, 'intermediate_size': 768, 'shared_intermediate_size': 1536,
        'mamba_n_heads': 128, 'mamba_d_head': 64, 'mamba_d_state': 128,
        'mamba_d_conv': 4, 'mamba_chunk_size': 256, 'mamba_n_groups': 1,
        'mamba_expand': 2, 'num_attention_heads': 32, 'num_key_value_heads': 8,
        'num_experts_per_tok': 10, 'num_routed_experts': 72,
        'attention_multiplier': 0.0078125, 'embedding_multiplier': 12,
        'residual_multiplier': 0.22, 'logits_scaling': 16,
    }
    assert {k: GRANITE[k] for k in widths} == widths
    assert GRANITE['layer_types'] == ['mamba'] * 5 + ['attention'] + ['mamba'] * 4
    assert (GRANITE['num_hidden_layers'], GRANITE['num_local_experts'],
            GRANITE['vocab_size']) == (10, 36, 50176)
    if catalog.is_file():  # every other published number is the catalog's
        published = next(
            json.loads(line)['config'] for line in catalog.read_text().splitlines()
            if '"granite-4.0-h-small"' in line
        )
        for key, value in published.items():
            if key not in GRANITE['reduced']:
                assert GRANITE[key] == value, key


def test_cells_offer_the_traffic_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    assert [c['name'] for c in manifest.data['workloads']] == [
        'mistral7b.batch_generate', 'mistral7b.chat_steady',
        'granite-4.0-h-small.batch_generate',
    ]
    assert all(c['chips'] == 1 for c in manifest.data['workloads'])
    closed = manifest.load('workloads', 'granite-4.0-h-small.batch_generate')
    assert closed['driver'] == 'granite_closed'
    assert closed['traffic']['prompts_per_call'] == 192
    assert closed['traffic']['prompt_tokens'] == {'dist': 'loguniform', 'lo': 128, 'hi': 1024}
    assert closed['traffic']['output_tokens'] == {'dist': 'fixed', 'value': 128}
    assert closed['sampling'] == {'temperature': 0.5, 'top_p': 0.95}
    # Held back (its spread is the seed's): the file stays as the issue states it.
    rag = manifest.load('workloads', 'mistral7b.rag_sessions')
    assert rag['driver'] == 'engine_open' and rag['sampling'] == {'temperature': 0.0}
    assert rag['traffic']['shared_prefix'] == {'sessions': 6, 'tokens': 1024}
    assert rag['traffic']['prompt_tokens'] == {'dist': 'loguniform', 'lo': 64, 'hi': 256}
    assert rag['traffic']['output_tokens'] == {'dist': 'loguniform', 'lo': 16, 'hi': 192}
    assert rag['traffic']['drain_limit_s'] == 40.0 and rag['traffic']['rate_rps'] > 0
    assert {m['name'] for m in manifest.metrics_of(
        'end_to_end', 'granite-4.0-h-small.batch_generate')} == {'gen_tok_s', 'setup_s'}
    # Every reader of the closed-loop cells that names no shape of
    # ``mistral7b`` reads the hybrid cell too, under the name it has; but
    # ``engine.serving_compile_ms.batch`` and ``engine.reprefill_share.batch``,
    # whose lists ``test_spans_readers.py`` holds to their one cell.
    assert {m['name'] for m in manifest.metrics_of(
        'per_layer', 'granite-4.0-h-small.batch_generate')} == {
        'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
        'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
        'model.hybrid_decode_step_ms.batch', 'model.hybrid_decode_bw_share.batch',
        'model.ssm_time_share.batch', 'model.moe_time_share.batch',
        'model.moe_held_pair_share.batch',
    }


def _ctx(config=GRANITE):
    return SimpleNamespace(config=config, device_kind='TPU v5e', capture=None)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the counters (the parent commit), or a run without
    a traced slice, leaves the metric out and raises nothing."""
    obs = {'flight': [{'kind': 'decode', 'batch': 4, 'tokens': 32}],
           'counters': {'mean_context_tokens': 500.0}, 'trace': None}
    assert hybrid.moe_held_pair_share(_ctx(), obs) is None
    assert hybrid.decode_bw_share(_ctx(), obs, '^jit_hybrid_window_fn') is None
    assert hybrid.scope_time_share(_ctx(), obs, 'distllm\\.moe') is None
    assert hybrid.prefix_hit_share(_ctx(), {'flight': []}) is None


def test_readers_read_counters_and_the_trace_summary():
    flight = [
        {'kind': 'decode', 'batch': 64, 'tokens': 512, 'moe_pairs': 51200, 'moe_pairs_held': 25000},
        {'kind': 'decode', 'batch': 64, 'tokens': 512, 'moe_pairs': 51200, 'moe_pairs_held': 26200},
        {'kind': 'request', 'prompt_tokens': 1200, 'cached_tokens': 1024},
        {'kind': 'request', 'prompt_tokens': 1100, 'cached_tokens': 0},
    ]
    obs = {
        'flight': flight, 'counters': {'mean_context_tokens': 500.0},
        'trace': {'busy_s': 4.0, 'module_s': {'jit_hybrid_window_fn(1)': 3.2},
                  'module_n': {'jit_hybrid_window_fn(1)': 16}},
        'scope_s': {'distllm.moe': 1.0, 'distllm.ssm_decode': 0.9,
                    'distllm.ssm_prefill': 0.1, '': 2.0},
    }
    assert hybrid.moe_held_pair_share(_ctx(), obs) == pytest.approx(50.0)
    assert hybrid.prefix_hit_share(_ctx(), obs) == pytest.approx(100 * 1024 / 2300)
    # 3.2 s over 16 runs x 8 steps = 25 ms a step against 17.7 ms of bytes.
    share = hybrid.decode_bw_share(_ctx(), obs, '^jit_hybrid_window_fn')
    assert share == pytest.approx(100 * 14.5e9 / 819e9 / 0.025, rel=0.02) and share < 100
    assert hybrid.scope_time_share(_ctx(), obs, 'distllm\\.moe') == pytest.approx(25.0)
    assert hybrid.scope_time_share(_ctx(), obs, 'distllm\\.ssm_') == pytest.approx(25.0)


def _rehearse(cell: str, trace_flag: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, str(ROOT / 'benchmarks/run.py'), '--workload', cell,
         '--seed', '3000000007', '--seconds', '1', '--trace', str(trace_flag),
         '--allow-cpu', '--manifest', str(REHEARSAL)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_rehearsal_of_the_hybrid_cell():
    line = _rehearse('tiny-granite.batch_generate', 0)
    assert line['correct'] is True and line['failed'] == 0 < line['attempted']
    assert line['metrics'] == {} and set(line['rehearsal_metrics']) == {'gen_tok_s', 'setup_s'}
    detail = line['detail']
    assert detail['compiles_in_window'] == 0 and detail['state_pool_bytes'] > 0
    # Both limits of the check had something to read: 4 rows x 2 of their 16
    # tokens, and the state each row left in the pool, layer by layer.
    assert detail['token_gap_positions'] == 8 and len(detail['ssm_state_errors']) == 3
    # float32 on both sides here
    assert detail['ssm_state_error_max'] < 1e-5 and detail['ssm_slow_heads_error'] < 1e-5
    calls = detail['calls']
    assert calls and all(c['steps_tokens'] == calls[0]['steps_tokens'] for c in calls)
    traced = _rehearse('tiny-granite.batch_generate', 1)['rehearsal_metrics']
    assert {'engine.decode_occupancy.batch', 'engine.window_host_ms.batch',
            'engine.compiles_in_window.batch', 'model.moe_held_pair_share.batch'} == set(traced)
    assert 30 < traced['model.moe_held_pair_share.batch']['value'] < 70


def test_rehearsal_of_the_rag_cell_hits_the_prefix_cache():
    line = _rehearse('tiny-mistral.rag_sessions', 1)
    assert line['correct'] is True and line['failed'] == 0 < line['attempted']
    assert line['detail']['compiles_in_window'] == 0
    assert line['rehearsal_metrics']['engine.prefix_hit_share.rag']['value'] > 30


def test_the_hybrid_cell_fails_at_once_without_the_model_module(tmp_path):
    """On a checkout that lacks ``models/granite_hybrid.py`` (the parent
    commit) the driver's first import fails: exit code non-zero, nothing
    allocated, no result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(ROOT / 'distllm_tpu', tree / 'distllm_tpu',
                    ignore=shutil.ignore_patterns('granite_hybrid.py', '__pycache__', '_build', '*.so'))
    shutil.copy(ROOT / 'BENCHMARK.json', tree / 'BENCHMARK.json')
    done = subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-granite.batch_generate', '--seed', '1', '--seconds', '1', '--trace', '0',
         '--allow-cpu', '--manifest', str(tree / 'benchmarks/tests/rehearsal_hybrid/BENCHMARK.json')],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS='cpu'),
        cwd=tree, timeout=300,
    )
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'granite_hybrid' in done.stderr


def test_scope_seconds_reads_the_ops_metadata_and_counts_each_instant_once():
    """The reduction from the profiler's protobuf to seconds by named scope,
    on a hand-made ``XSpace``: the scope is in the op's metadata (``tf_op``,
    as a string or a reference), the grouped matmul's call is known by its
    name, and a ``while`` keeps only what no child covers."""
    xplane_pb2 = pytest.importorskip('tensorflow.tsl.profiler.protobuf.xplane_pb2')
    space = xplane_pb2.XSpace()
    host = space.planes.add()
    host.name = '/host:CPU'
    plane = space.planes.add()
    plane.name = '/device:TPU:0'
    plane.stat_metadata[1].name = 'tf_op'
    plane.stat_metadata[2].name = 'jit(f)/while/body/distllm.ssm_decode/mul:'

    def metadata(key, name, text=None, ref=None):
        entry = plane.event_metadata[key]
        entry.id, entry.name = key, name
        if text is not None or ref is not None:
            stat = entry.stats.add()
            stat.metadata_id = 1
            if text is not None:
                stat.str_value = text
            else:
                stat.ref_value = ref

    metadata(1, '%while = while')
    metadata(2, '%fusion.1 = fusion', text='jit(f)/while/body/distllm.moe/dot:')
    metadata(3, '%ragged-dot-none.2 = custom-call', text='ragged-dot-none:')
    metadata(4, '%fusion.9 = fusion', ref=2)
    line = plane.lines.add()
    line.name = 'XLA Ops'
    for key, offset, duration in ((1, 0, 10_000_000), (2, 1_000_000, 2_000_000),
                                  (3, 3_000_000, 3_000_000), (4, 7_000_000, 1_000_000)):
        event = line.events.add()
        event.metadata_id, event.offset_ps, event.duration_ps = key, offset, duration
    got = hybrid.scope_seconds(space)
    assert got == pytest.approx(
        {'distllm.moe': 5e-6, 'distllm.ssm_decode': 1e-6, '': 4e-6}
    )
    assert hybrid.scope_seconds(xplane_pb2.XSpace()) is None
    assert hybrid.collect_scope_seconds(None) is None
