"""The cell PR 48 adds (``ouro-2.6b.batch_mcqa``): its byte and operation
account against the issue's arithmetic and the program's own parameter tree,
its files against what the issue states, its readers on hand-made records,
and a CPU rehearsal through the harness at toy sizes (``rehearsal_ouro``;
never a measurement)."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import ouro_bytes
from benchmarks.manifest import Manifest
from benchmarks.readers import ouro

ROOT = Path(__file__).resolve().parents[2]
CELL = 'ouro-2.6b.batch_mcqa'
MODEL = json.loads((ROOT / 'benchmarks/configs/ouro-2.6b.json').read_text())
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
OWN = [
    'model.ouro_decode_step_ms.batch', 'model.ouro_decode_bw_share.batch',
    'kernel.paged_attn_time_share.ouro', 'kernel.paged_attn_roofline_share.ouro',
    'model.loop_last_pass_share.batch',
]
SHARED = [
    'engine.window_host_ms.batch', 'engine.decode_occupancy.batch',
    'engine.compiles_in_window.batch', 'engine.idle_outside_spans_share.batch',
    'model.head_sample_time_share.batch', 'kernel.full_attn_time_share.batch',
]


def test_byte_account_matches_the_issues_arithmetic_and_the_programs_tree():
    import jax

    from distllm_tpu.models import ouro as program

    assert ouro_bytes.layer_params(MODEL) == 51_388_416
    assert ouro_bytes.stack_params(MODEL) == 2_466_643_968
    held = ouro_bytes.held_params(MODEL)
    # the issue's 2.668 G and the gate's 2049 beside it
    assert held == 2_466_643_968 + 201_326_592 + 2048 + 2049
    cfg = program.OuroConfig.from_hf_config(MODEL)
    shapes = jax.eval_shape(
        lambda: program.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == held
    assert held * 2 == MODEL['deployment']['weights_bytes']
    assert held * 2 == pytest.approx(5.34e9, rel=1e-3)
    # A token's rows: 192 planes x (K + V) x 2048 lanes x 2 bytes = 1.5 MiB.
    assert ouro_bytes.planes(MODEL) == 192 == cfg.num_planes
    assert ouro_bytes.kv_bytes_per_token(MODEL) == 1536 * 1024
    assert MODEL['deployment']['kv_bytes_per_token'] == 1536 * 1024
    # A step streams the stack four times and the head once: 19.9 GB.
    assert ouro_bytes.step_weight_params(MODEL) * 2 == pytest.approx(19.93e9, rel=1e-3)
    # 2 x 16 heads x (128 + 128) operations a cached token a plane.
    assert ouro_bytes.attn_flops(MODEL, 1) == 192 * 2 * 16 * 256
    # 12 rows at 4,200 cached tokens: the weights, 6.6 GB of K/V, the writes
    step = ouro_bytes.decode_step_bytes(MODEL, 12, 4_200)
    assert step == pytest.approx(19.93e9 + 4_212 * 1536 * 1024, rel=1e-3)
    # The pool the configuration asks for: 24 MiB a block of 16.
    engine = MODEL['engine']
    assert ouro_bytes.kv_bytes(MODEL, 16) == 24 * 2**20
    assert ouro_bytes.kv_bytes(MODEL, engine['num_blocks'] * 16) == (
        engine['num_blocks'] * 24 * 2**20
    )


def test_cell_and_configuration_are_what_the_issue_states():
    manifest = Manifest(ROOT / 'BENCHMARK.json')
    assert all(c['chips'] == 1 for c in manifest.data['workloads'])
    entry = next(c for c in manifest.data['configs'] if c['name'] == 'ouro-2.6b')
    assert len(entry['why']) <= 200
    assert entry['source'] == MODEL['source'] == (
        'https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json'
    )
    assert entry['reduced'] == MODEL['reduced'] == []
    if CATALOG.exists():  # every published key, unchanged
        row = next(
            json.loads(line) for line in CATALOG.read_text().splitlines()
            if '"Ouro-2.6B"' in line
        )
        assert entry['source'] == row['source_url']
        for key, value in row['config'].items():
            assert MODEL[key] == value, key
    assert MODEL['deployment']['chips'] == 1
    assumed = ' '.join(MODEL['assumed'])
    for word in ('input_layernorm_2', 'post_attention_layernorm_2', 'final norm',
                 't * num_hidden_layers + l', 'bias', 'early_exit_gate',
                 'float32', 'Q-exit', 'normal(0, 0.02)'):
        assert word in assumed, word
    engine = MODEL['engine']
    assert {k: engine[k] for k in (
        'block_size', 'prefill_chunk_tokens', 'max_model_len', 'decode_steps',
        'attn_backend',
    )} == {
        'block_size': 16, 'prefill_chunk_tokens': 512, 'max_model_len': 1024,
        'decode_steps': 8, 'attn_backend': 'auto',
    }
    assert MODEL['expect_attn_backend'] == 'pallas' and MODEL['dtype'] == 'bfloat16'
    cell = manifest.cell(CELL)
    assert cell['chips'] == 1 and cell['config'] == 'ouro-2.6b'
    assert len(cell['why']) <= 200 and '24 prompts' in cell['why']
    workload = manifest.load('workloads', CELL)
    assert workload['driver'] == 'ouro_closed'
    traffic = workload['traffic']
    assert traffic['prompts_per_call'] == 24 and traffic['schedule_seed'] == 0
    assert traffic['prompt_tokens'] == {'dist': 'loguniform', 'lo': 64, 'hi': 512}
    assert traffic['output_tokens'] == {'dist': 'fixed', 'value': 256}
    assert workload['sampling'] == {'temperature': 0.5, 'top_p': 0.95}
    assert workload['warmup'] == {'replica_calls': 1}
    assert workload['trace']['seconds'] == 5.0
    reported = {m['name'] for m in manifest.metrics_of('per_layer', CELL)}
    assert reported == set(OWN) | set(SHARED)
    for name in OWN:
        entry = next(m for m in manifest.data['per_layer'] if m['name'] == name)
        assert entry['workloads'] == [CELL] and entry['moves'] == 'gen_tok_s'
        assert set(entry) == {
            'name', 'unit', 'better', 'source', 'layer', 'moves', 'workloads',
        }
        assert manifest.load('metrics', name)['name'] == name
    ends = {m['name'] for m in manifest.metrics_of('end_to_end', CELL)}
    assert ends == {'gen_tok_s', 'setup_s'}


def _ctx(capture=None):
    return SimpleNamespace(
        config=MODEL, device_kind='TPU v5e',
        capture=capture or SimpleNamespace(t_start=0.0, t_stop=10.0),
    )


def _obs():
    flight = [
        {'kind': 'decode', 'batch': 12, 'kv_blocks': 260, 't0_s': 1.0,
         'loop_exit_pass': [0, 0, 0, 96], 'tokens': 96},
        {'kind': 'decode', 'batch': 10, 'kv_blocks': 240, 't0_s': 2.0,
         'loop_exit_pass': [0, 0, 2, 78], 'tokens': 80},
        {'kind': 'prefill', 'tokens': 300},
    ]
    return {
        'flight': flight,
        'trace': {
            'busy_s': 0.8,
            'module_s': {'jit_ouro_window_fn(123)': 0.8},
            'module_n': {'jit_ouro_window_fn(123)': 2},
        },
        'kernel_call_s': {
            'jit_ouro_window_fn(123) distllm.attn_full': 0.2,
            'jit_ouro_prefill_fn(9) distllm.attn_full': 0.04,
            'jit_other(1) distllm.attn_full': 5.0,
        },
    }


def test_readers_read_what_they_say():
    ctx, obs = _ctx(), _obs()
    window = r'^jit_ouro_window_fn'
    scoped = r'^jit_ouro_window_fn\S* distllm\.attn_full$'
    tokens = 16 * (260 + 240) / 2
    want = 100.0 * ouro_bytes.decode_step_bytes(MODEL, 11, tokens) / 819e9 / 0.05
    assert ouro.decode_bw_share(ctx, obs, window) == pytest.approx(want)
    both = r'^jit_ouro_(window|prefill)_fn\S* distllm\.attn_full$'
    assert ouro.paged_attn_time_share(ctx, obs, both) == pytest.approx(30.0)
    least = ouro_bytes.kv_bytes(MODEL, 8 * 16 * 500) / 819e9
    assert ouro.paged_attn_roofline_share(ctx, obs, scoped) == pytest.approx(
        100.0 * least / 0.2
    )
    assert ouro.last_pass_share(ctx, obs) == pytest.approx(100.0 * 174 / 176)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    """The parent of the PR that added them: no such program, scope or
    counter; an untraced run: no trace at all."""
    ctx = _ctx()
    bare = {'flight': [{'kind': 'decode', 'batch': 3, 'kv_blocks': 9}],
            'trace': None, 'kernel_call_s': None}
    assert ouro.decode_bw_share(ctx, bare, '^jit_ouro_window_fn') is None
    assert ouro.paged_attn_time_share(ctx, bare, 'x') is None
    assert ouro.paged_attn_roofline_share(ctx, bare, 'x') is None
    assert ouro.last_pass_share(ctx, bare) is None
    other = _obs()
    other['kernel_call_s'] = {'jit_window_fn(1) ': 0.3}
    other['trace']['module_s'] = {'jit_window_fn(1)': 0.8}
    other['trace']['module_n'] = {'jit_window_fn(1)': 2}
    assert ouro.decode_bw_share(ctx, other, '^jit_ouro_window_fn') is None
    assert ouro.paged_attn_time_share(ctx, other, '^jit_ouro') is None
    assert ouro.paged_attn_roofline_share(ctx, other, '^jit_ouro') is None


def test_the_reference_takes_nothing_from_the_program():
    text = (ROOT / 'benchmarks/reference_ouro.py').read_text()
    assert 'import distllm_tpu' not in text and 'from distllm_tpu' not in text
    assert "default_matmul_precision('highest')" in text


def test_rehearsal_runs_the_cell_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, 'benchmarks/run.py', '--workload', 'tiny-ouro.batch_mcqa',
         '--seed', '3000000123', '--seconds', '1', '--trace', '0', '--allow-cpu',
         '--manifest', 'benchmarks/tests/rehearsal_ouro/BENCHMARK.json'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['failed'] == 0
    assert line['metrics'] == {} and 'gen_tok_s' in line['rehearsal_metrics']
    detail = line['detail']
    assert detail['check_planes'] == [0, 11]
    assert detail['loop'] == {
        'loop_window_form': 'passes_rolled_layers_unrolled', 'kv_walk_keys': None,
    }
    assert detail['kv_pool']['shape'][0] == 12  # T * L planes
    assert detail['check_call']['preemptions'] == 0
    exits = detail['window_engine']['loop_exit_pass']
    assert exits[:3] == [0, 0, 0] and exits[3] > 0
    assert detail['kv_error_max_row'] < 1e-4 and detail['token_gap_max_std'] < 1e-3
