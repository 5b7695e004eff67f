"""The cell PR 54 adds (``sdar-30b-a3b-chat.batch_fixed_length``): its byte
account against the issue's arithmetic, its files against what the issue
states, its readers on hand-made records, the check's own scoring on a toy,
and a CPU rehearsal through the harness at toy sizes (``rehearsal_sdar``;
never a measurement)."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import sdar_bytes, traffic
from benchmarks.drivers import sdar_closed
from benchmarks.manifest import Manifest
from benchmarks.readers import sdar

ROOT = Path(__file__).resolve().parents[2]
CELL = 'sdar-30b-a3b-chat.batch_fixed_length'
MODEL = json.loads(
    (ROOT / 'benchmarks/configs/sdar-30b-a3b-chat.json').read_text()
)
NEW_METRICS = {
    'model.sdar_decode_step_ms.batch',
    'model.sdar_decode_bw_share.batch',
    'kernel.paged_attn_roofline_share.sdar',
    'engine.forwards_per_token.batch',
    'model.unmask_time_share.batch',
}
SHARED_METRICS = {
    'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
    'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
    'engine.stall_s.batch', 'engine.stalls_in_window.batch',
    'engine.serve_self_share.batch', 'model.moe_time_share.batch',
    'model.moe_held_pair_share.batch', 'kernel.full_attn_time_share.batch',
    'model.head_sample_time_share.batch',
}


def test_byte_account_matches_the_issues_arithmetic():
    # A layer as one of 8 chips holds it: 94,638,336 parameters; all 48:
    # 4,542,640,128; with the whole vocabulary 5,164,972,032 = 10.33 GB.
    assert sdar_bytes.layer_params(MODEL) == 94_638_336
    held = 48 * sdar_bytes.layer_params(MODEL) + 2 * sdar_bytes.head_params(MODEL)
    assert held + MODEL['hidden_size'] == 5_164_972_032  # the final norm too
    # A forward reads the layers and, four times in five, the head: 9.58 GB.
    assert 2 * sdar_bytes.weight_params(MODEL) == pytest.approx(9.58e9, rel=0.002)
    assert sdar_bytes.forwards_a_block(MODEL) == 5
    assert sdar_bytes.forwards_a_window(MODEL) == 10
    # 96 KiB a cached token over the 48 layers
    assert sdar_bytes.kv_bytes(MODEL, 1) == 96 * 1024
    # 32 queries a KV head: 4 x 32 x 128 x 4 operations a cached token a
    # layer, still far under the ridge
    assert sdar_bytes.attn_flops(MODEL, 1) == 48 * 4 * 32 * 128 * 4
    assert (
        sdar_bytes.attn_flops(MODEL, 1) / 197e12
        < sdar_bytes.kv_bytes(MODEL, 1) / 819e9
    )
    # 48 rows at a mean context of 540: about 12 GB a forward, 15 ms.
    step = sdar_bytes.forward_bytes(MODEL, 48 * 540, 48)
    assert step == pytest.approx(12.2e9, rel=0.03)


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    entry = next(
        c for c in manifest.data['configs'] if c['name'] == 'sdar-30b-a3b-chat'
    )
    assert entry == manifest.data['configs'][-1]  # appended, nothing moved
    assert entry['source'] == MODEL['source'] and entry['reduced'] == MODEL['reduced']
    assert entry['reduced'] == ['num_experts'] and MODEL['published'] == {'num_experts': 128}
    assert (MODEL['num_hidden_layers'], MODEL['num_experts'],
            MODEL['num_routed_experts'], MODEL['vocab_size']) == (48, 16, 128, 151936)
    assert (MODEL['block_length'], MODEL['mask_token_id']) == (4, 151669)
    assert len(MODEL['assumed']) >= 8 and 'v5e-8' in MODEL['deployment']
    engine = MODEL['engine']
    assert (engine['max_num_seqs'], engine['num_blocks'], engine['decode_steps'],
            engine['denoise_steps'], engine['max_model_len']) == (48, 2560, 8, 4, 2048)
    assert not engine['enable_prefix_cache'] and MODEL['expect_attn_backend'] == 'pallas'
    assert set(engine) <= set(MODEL['engine_notes'])
    cell = manifest.cell(CELL)
    assert cell == manifest.data['workloads'][-1] and cell['chips'] == 1
    assert 'its share' in cell['why'] and len(cell['why']) <= 200 >= len(entry['why'])
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'sdar_closed'
    spec = workload['traffic']
    assert spec['prompts_per_call'] == 48 and spec['schedule_seed'] == 0
    assert spec['prompt_tokens'] == {'dist': 'loguniform', 'lo': 64, 'hi': 512}
    assert spec['output_tokens'] == {'dist': 'fixed', 'value': 512}
    assert workload['sampling'] == {'temperature': 0.5, 'top_p': 0.95}
    assert {m['name'] for m in manifest.metrics_of('end_to_end', CELL)} == {
        'gen_tok_s', 'setup_s',
    }
    reported = {m['name'] for m in manifest.metrics_of('per_layer', CELL)}
    assert NEW_METRICS | SHARED_METRICS <= reported
    for name in NEW_METRICS:  # each a data file whose reader is there
        module, func = manifest.load('metrics', name)['reader'].split(':')
        assert hasattr(__import__(f'benchmarks.readers.{module}', fromlist=['x']), func)
        entry = next(m for m in manifest.data['per_layer'] if m['name'] == name)
        assert entry['workloads'] == [CELL] and entry['moves'] == 'gen_tok_s'


def test_the_calls_size_is_what_the_issue_reckoned():
    workload = json.loads(
        (ROOT / f'benchmarks/workloads/{CELL}.json').read_text()
    )['traffic']
    lengths = sorted(traffic.sizes(
        workload['prompt_tokens'], 48, traffic.schedule_rng(workload, 'call')
    ))
    out = workload['output_tokens']['value']
    assert lengths[0] >= 64 and lengths[-1] + out <= MODEL['engine']['max_model_len']
    ends = sum(n + out for n in lengths)
    assert ends == pytest.approx(34_900, rel=0.02)  # tokens a call ends at
    pages = sum(-(-(n + out) // 16) for n in lengths)
    assert pages < MODEL['engine']['num_blocks'] - 1  # no row ever waits
    # every remainder of a prompt over its blocks is in the call
    assert {n % 4 for n in lengths} == {0, 1, 2, 3}
    # 128 blocks a row, to a token's remainder
    scored = sdar_closed.scored_blocks(lengths[0], out, 4)
    assert len(scored) == 3 and scored[0] == lengths[0] // 4 * 4
    assert scored[-1] == (lengths[0] + out) // 4 * 4 - 4


def _ctx(capture=None, model=MODEL):
    return SimpleNamespace(config=model, device_kind='TPU v5e', capture=capture)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """A program without the counters (the parent commit), or a run without
    a traced slice, leaves the metric out and raises nothing."""
    old = {'kind': 'decode', 'batch': 4, 'tokens': 32, 'kv_blocks': 90}
    for flight in ([], [old]):
        obs = {'flight': flight, 'counters': {}, 'trace': None}
        assert sdar.forwards_per_token(_ctx(), obs) is None
        assert sdar.forward_ms(_ctx(), obs, '^jit_sdar') is None
        assert sdar.decode_bw_share(_ctx(), obs, '^jit_sdar') is None
        assert sdar.paged_attn_roofline_share(_ctx(), obs, 'x') is None
        traced = dict(obs, kernel_call_s={}, trace={
            'busy_s': 1.0, 'op_s': {}, 'module_s': {}, 'module_n': {}})
        assert sdar.decode_bw_share(_ctx(), traced, '^jit_sdar') is None
        assert sdar.paged_attn_roofline_share(_ctx(), traced, 'x') is None
    # another configuration's file (no denoise_steps): nothing, no error
    other = {'engine': {'decode_steps': 8}}
    traced = {'flight': [], 'trace': {
        'module_s': {'jit_sdar_window_fn': 1.0}, 'module_n': {'jit_sdar_window_fn': 1}}}
    assert sdar.forward_ms(_ctx(model=other), traced, '^jit_sdar') is None


def test_readers_read_the_counters_and_the_kernels_calls():
    windows = [
        {'kind': 'decode', 'batch': 48, 'tokens': 384, 't0_s': 10.0 + i,
         'kv_blocks': 1700, 'forwards': 480, 'blocks': 96,
         'decided': 384 - (20 if i == 0 else 0)}
        for i in range(3)
    ]
    capture = SimpleNamespace(t_start=10.5, t_stop=12.5)  # holds two of them
    program = 'jit_sdar_window_fn(123)'
    obs = {
        'flight': windows, 'counters': {},
        'trace': {'busy_s': 4.0, 'module_s': {program: 0.51},
                  'module_n': {program: 3}, 'op_s': {}},
        'kernel_call_s': {
            f'{program} distllm.attn_full': 0.12,
            f'{program} distllm.moe': 9.0,  # the grouped matmul: not read
            'jit_sdar_prefill_fn(9) distllm.attn_full': 5.0,
        },
    }
    assert sdar.forwards_per_token(_ctx(), obs) == pytest.approx(1440 / 1132)
    # 0.51 s over 3 runs x 10 forwards = 17 ms a forward
    assert sdar.forward_ms(_ctx(), obs, '^jit_sdar_window_fn') == pytest.approx(17.0)
    moved = sdar_bytes.forward_bytes(MODEL, 16 * 1700, 48)
    share = sdar.decode_bw_share(_ctx(), obs, '^jit_sdar_window_fn')
    assert share == pytest.approx(100 * moved / 819e9 / 0.017) and 50 < share < 100
    pattern = Manifest(ROOT / 'BENCHMARK.json').load(
        'metrics', 'kernel.paged_attn_roofline_share.sdar'
    )['args']['pattern']
    asked = 2 * 10 * sdar_bytes.kv_bytes(MODEL, 16 * 1700)
    roofline = sdar.paged_attn_roofline_share(_ctx(capture), obs, pattern)
    assert roofline == pytest.approx(100 * asked / 819e9 / 0.12) and roofline < 100


def _rehearse(trace_flag: int, tree: Path = ROOT) -> subprocess.CompletedProcess:
    manifest = tree / 'benchmarks/tests/rehearsal_sdar/BENCHMARK.json'
    return subprocess.run(
        [sys.executable, str(tree / 'benchmarks/run.py'), '--workload',
         'tiny-sdar.batch_fixed_length', '--seed', '3000000007', '--seconds',
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
    assert detail['compiles_in_window'] == 0 and len(detail['by_row']) == 6
    # float32 on both sides here
    assert detail['token_gap_mean_std'] <= detail['token_gap_max_std'] < 1e-3
    assert detail['confidence_shortfall_mean'] < 1e-3
    assert detail['kv_content_error'] < 1e-5 > detail['kv_last_content_error']
    assert detail['check_preemptions'] == 0
    assert detail['window_engine']['preemptions'] == 0
    assert {'weights', 'engine', 'warmup_calls', 'programs'} <= set(
        detail['setup_split_s']
    )
    traced = line['rehearsal_metrics']
    # What needs no device trace reads on the CPU too.
    assert {'engine.decode_occupancy.batch', 'engine.window_host_ms.batch',
            'model.moe_held_pair_share.batch',
            'engine.forwards_per_token.batch'} <= set(traced)
    assert 30 < traced['model.moe_held_pair_share.batch']['value'] < 70
    # five forwards a block of four, a little more for the given positions
    assert 1.25 <= traced['engine.forwards_per_token.batch']['value'] < 1.45


def test_the_check_reads_a_wrong_program(monkeypatch):
    """The cell's own scoring on the toy: right reads zero; a program that
    keeps the LEAST confident position, and one whose commit is skipped,
    each break a limit of their own."""
    sys.path.insert(0, str(ROOT / 'tests'))
    import sdar_toy as toy

    from benchmarks import reference_sdar as reference
    from distllm_tpu.generate.engine.engine import SamplingParams
    from distllm_tpu.ops import sampling

    def scored(engine, params, hf):
        prompt = toy.prompt(np.random.default_rng(3), 21)
        before = engine.flight.total_recorded
        (output,) = engine.generate_ids(
            [prompt], SamplingParams(temperature=0.0, max_tokens=22)
        )
        record = [
            r for r in engine.flight.snapshot()[before - engine.flight.total_recorded:]
            if r['kind'] == 'request'
        ][0]
        model = {**hf, 'engine': {'denoise_steps': 4, 'block_size': toy.PAGE}}
        pages = sdar_closed._pages(engine, record, (0, 2))
        return sdar_closed.score_row(
            params, model, prompt, output, record['decided_at'], pages, [64]
        )

    hf, params, engine = toy.make_engine()
    right = scored(engine, params, hf)
    assert max(right['gaps']) < 1e-3 > max(right['shortfalls'])
    assert right['kv_layer_0'] < 1e-5 > right['kv_layer_2']

    real = sampling.select_unmask
    monkeypatch.setattr(
        sampling, 'select_unmask',
        lambda conf, masked, count, tau=None: real(-conf, masked, count, tau),
    )
    hf, params, engine = toy.make_engine()
    wrong = scored(engine, params, hf)
    assert np.mean(wrong['shortfalls']) > reference.CONFIDENCE_LIMIT
    monkeypatch.undo()

    from distllm_tpu.models import sdar as model_module

    block_pass, calls = model_module._block_pass, []

    def skip_commit(params, cfg, rope, backend, ids, start, k, v, tables, live):
        calls.append(1)
        out = block_pass(params, cfg, rope, backend, ids, start, k, v, tables, live)
        return out if len(calls) % 2 else (out[0], k, v, out[3])

    monkeypatch.setattr(model_module, '_block_pass', skip_commit)
    hf, params, engine = toy.make_engine()
    wrong = scored(engine, params, hf)
    assert wrong['kv_layer_0'] > reference.KV_CONTENT_LIMIT


def test_the_cell_fails_at_once_without_the_model_module(tmp_path):
    """On a checkout that lacks ``models/sdar.py`` (the parent commit) the
    driver's first import fails: exit code non-zero, nothing allocated, no
    result line."""
    import shutil

    tree = tmp_path / 'parent'
    shutil.copytree(ROOT / 'benchmarks', tree / 'benchmarks')
    shutil.copytree(
        ROOT / 'distllm_tpu', tree / 'distllm_tpu',
        ignore=shutil.ignore_patterns('sdar.py', '__pycache__', '_build', '*.so'),
    )
    (tree / 'distllm_tpu/models/__init__.py').write_text('')
    done = _rehearse(0, tree)
    assert done.returncode != 0 and done.stdout.strip() == ''
    assert 'sdar' in done.stderr
