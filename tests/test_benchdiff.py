"""Bench trajectory gate (``scripts/benchdiff.py``): the fast-tier smoke
runs it over two driver-shaped records built in ``tmp_path`` — one that
crashed before emitting, one clean full record (the known embed/gen
deltas must appear, exit 0) — and over an injected regression (exit
nonzero) — the acceptance shape of the ISSUE 11 tentpole."""

from __future__ import annotations

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BENCHDIFF = REPO / 'scripts' / 'benchdiff.py'

_spec = importlib.util.spec_from_file_location('benchdiff', BENCHDIFF)
benchdiff = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(benchdiff)


# The two record shapes the driver writes: a run that died before its
# contract line (rc 1, traceback tail, nothing parsed) and a clean one.
_R01 = {
    'n': 1,
    'cmd': 'if [ -f bench.py ]; then python bench.py; else exit 0; fi',
    'rc': 1,
    'tail': 'Traceback (most recent call last):\n  ...\n'
    'jaxlib.xla_extension.XlaRuntimeError: UNAVAILABLE\n',
    'parsed': None,
}
_R02 = {
    'n': 2,
    'cmd': 'if [ -f bench.py ]; then python bench.py; else exit 0; fi',
    'rc': 0,
    'parsed': {
        'metric': 'embeddings/sec/chip',
        'value': 1619.88,
        'unit': 'emb/s',
        'vs_baseline': 0.585,
        'mfu': 0.463,
        'device': 'TPU v5 lite',
        'gen_metric': 'gen tokens/sec/chip',
        'gen_value': 184.18,
        'gen_unit': 'tok/s',
        'gen_vs_baseline': 0.093,
        'gen_mfu': 0.0135,
        'gen_n_tokens': 8192,
        'gen_attn_backend': 'xla',
    },
}
_R02['tail'] = json.dumps(_R02['parsed']) + '\n'


@pytest.fixture
def records(tmp_path):
    """``(r01_path, r02_path)`` written under ``tmp_path``."""
    paths = []
    for name, record in (('BENCH_r01.json', _R01), ('BENCH_r02.json', _R02)):
        path = tmp_path / name
        path.write_text(json.dumps(record, indent=2))
        paths.append(path)
    return tuple(paths)


def _run(*args):
    return subprocess.run(
        [sys.executable, str(BENCHDIFF), *map(str, args)],
        capture_output=True, text=True, timeout=120,
    )


def test_r01_r02_records_pass_and_report_known_deltas(records):
    """r01 crashed before emitting (no metrics); r02 is the last clean
    full record: 1619.88 emb/s and 184.18 tok/s appear as new metrics,
    and a new metric is never a regression."""
    proc = _run(records[0], records[1])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = proc.stdout
    assert '| value |' in out and '1619.88' in out
    assert '| gen_value |' in out and '184.18' in out
    assert '| mfu |' in out and '0.463' in out
    assert 'new' in out
    assert 'No regressions' in out
    # r01's empty payload is surfaced, not crashed over.
    assert 'r01' in out and 'no metrics' in out


def test_injected_regression_exits_nonzero(tmp_path, records):
    fake = {
        'n': 6,
        'rc': 0,
        'parsed': {
            'metric': 'embeddings/sec/chip',
            'value': 1400.0,       # 1619.88 -> 1400: -13.6%
            'unit': 'emb/s',
            'gen_value': 100.0,    # 184.18 -> 100: -45.7%
            'gen_mfu': 0.0135,     # unchanged: must NOT be flagged
        },
    }
    candidate = tmp_path / 'BENCH_r06.json'
    candidate.write_text(json.dumps(fake))
    proc = _run(
        records[0], records[1], candidate,
        '--markdown', tmp_path / 'trajectory.md',
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = proc.stdout
    assert 'REGRESSED' in out
    assert 'gen_value' in out and '-45.7%' in out
    assert (tmp_path / 'trajectory.md').read_text() == out
    # Within-threshold and informational metrics never gate.
    assert '| gen_mfu |' in out and 'gen_mfu' not in [
        line.split('`')[1]
        for line in out.splitlines()
        if line.startswith('- `')
    ]


def test_threshold_and_direction_semantics(tmp_path):
    base = tmp_path / 'a.json'
    base.write_text(json.dumps({
        'parsed': {'value': 100.0, 'gen_ttft_s': 1.0, 'n_tokens': 500}
    }))

    def candidate(**metrics):
        path = tmp_path / 'b.json'
        path.write_text(json.dumps({'parsed': metrics}))
        return path

    # Latency is lower-better: a rise beyond threshold regresses...
    proc = _run(
        base, candidate(value=100.0, gen_ttft_s=1.5, n_tokens=500)
    )
    assert proc.returncode == 1 and 'gen_ttft_s' in proc.stdout
    # ...a fall (plus a small within-threshold throughput dip) passes.
    proc = _run(
        base, candidate(value=98.0, gen_ttft_s=0.5, n_tokens=500)
    )
    assert proc.returncode == 0, proc.stdout
    # Informational counters never gate, even when they collapse.
    proc = _run(base, candidate(value=100.0, gen_ttft_s=1.0, n_tokens=1))
    assert proc.returncode == 0, proc.stdout
    # --strict-missing turns a lost gated metric into a failure.
    proc = _run(base, candidate(value=100.0))
    assert proc.returncode == 0
    proc = _run(base, candidate(value=100.0), '--strict-missing')
    assert proc.returncode == 1


def test_non_finite_metrics_never_crash_or_silently_pass(tmp_path):
    """bench records round-trip NaN/inf through json (allow_nan): the
    gate must neither crash formatting them nor let a NaN slide past
    every threshold comparison — a non-finite value reads as 'not
    reported' (lost under --strict-missing)."""
    base = tmp_path / 'a.json'
    base.write_text(json.dumps({'parsed': {'value': 100.0}}))
    cand = tmp_path / 'b.json'
    cand.write_text(json.dumps(
        {'parsed': {'value': float('nan'), 'gen_value': float('inf')}}
    ))
    proc = _run(base, cand)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert 'Traceback' not in proc.stderr
    assert 'value' in proc.stdout and 'lost' in proc.stdout
    proc = _run(base, cand, '--strict-missing')
    assert proc.returncode == 1


def test_library_surface_matches_cli(records):
    loaded = [benchdiff.load_record(path) for path in records]
    assert loaded[0]['metrics'] == {}
    assert loaded[1]['metrics']['value'] == 1619.88
    assert loaded[1]['metrics']['gen_value'] == 184.18
    regressions, lost = benchdiff.diff_records(loaded, threshold=0.05)
    assert regressions == [] and lost == []
    assert benchdiff.gate_direction('gen_value') == 'higher'
    assert benchdiff.gate_direction('gen_load_ttft_p95_s') == 'lower'
    assert benchdiff.gate_direction('warmup_secs') == 'lower'
    assert benchdiff.gate_direction('n_tokens') is None
    # gen_tier (KV-tier) metrics: warm/cold TTFT gate lower-better,
    # promotion overlap and hit rate higher-better, the speedup ratio
    # higher-better despite its 'ttft' substring, and raw spill /
    # promotion counts stay informational.
    assert benchdiff.gate_direction('gen_tier_warm_ttft_s') == 'lower'
    assert benchdiff.gate_direction('gen_tier_cold_ttft_s') == 'lower'
    assert benchdiff.gate_direction('gen_tier_warm_ttft_speedup') == 'higher'
    assert (
        benchdiff.gate_direction('gen_tier_promotion_overlap') == 'higher'
    )
    assert benchdiff.gate_direction('gen_tier_hit_rate') == 'higher'
    # gen_router (multi-replica tier) headline gates: the affinity-vs-RR
    # warm-TTFT speedup ratio and the replica-kill goodput both gate
    # higher-better (docs/routing.md).
    assert (
        benchdiff.gate_direction('gen_router_router_warm_ttft_speedup')
        == 'higher'
    )
    assert (
        benchdiff.gate_direction('gen_router_failover_goodput') == 'higher'
    )
    assert (
        benchdiff.gate_direction('gen_router_affinity_ttft_p95') == 'lower'
    )
    assert benchdiff.gate_direction('gen_tier_spills') is None
    assert benchdiff.gate_direction('gen_tier_promotions') is None
    assert benchdiff.gate_direction('gen_tier_spilled_blocks') is None


def test_gen_chaos_gate_directions():
    """ISSUE 15: goodput-under-fault and recoveries gate higher-better;
    shed metrics stay informational (shed volume is offered-load policy,
    not quality)."""
    assert benchdiff.gate_direction('gen_chaos_goodput_tokens') == 'higher'
    assert benchdiff.gate_direction('gen_chaos_recoveries') == 'higher'
    assert benchdiff.gate_direction('gen_chaos_tok_s') == 'higher'
    assert benchdiff.gate_direction('gen_chaos_shed_rate') is None
    assert benchdiff.gate_direction('gen_chaos_shed_requests') is None
    assert benchdiff.gate_direction('gen_chaos_retries') is None
    assert benchdiff.gate_direction('gen_chaos_quarantined') is None
    assert benchdiff.gate_direction('gen_chaos_faults_injected') is None


def test_gen_kvq_gate_directions():
    """ISSUE 17: the quantized-KV stage's accuracy fraction gates
    higher-better — a FALLING greedy match is a quality regression (the
    compression got lossier) and must trip the gate like a throughput
    fall. Byte/capacity evidence stays informational: pool bytes and
    capacity are geometry facts, not round-over-round quality."""
    assert benchdiff.gate_direction('gen_kvq_greedy_match') == 'higher'
    assert benchdiff.gate_direction('gen_kvq_int8_tok_s') == 'higher'
    assert benchdiff.gate_direction('gen_kvq_bf16_tok_s') == 'higher'
    assert (
        benchdiff.gate_direction('gen_kvq_int8_bw_util_measured') == 'higher'
    )
    assert benchdiff.gate_direction('gen_kvq_speedup') == 'higher'
    assert benchdiff.gate_direction('gen_kvq_int8_kv_pool_bytes') is None
    assert benchdiff.gate_direction('gen_kvq_kv_pool_bytes_ratio') is None
    assert benchdiff.gate_direction('gen_kvq_int8_capacity_blocks') is None
    assert (
        benchdiff.gate_direction('gen_kvq_int8_decode_bytes_accessed') is None
    )


def test_gen_history_gate_directions():
    """ISSUE 18: the telemetry stage's throughput/latency arms gate like
    every other serving stage; sentinel fire counts, burn rates and shed
    volume stay informational — they are schedule/policy facts, and the
    stage itself errors when the slow arm fails to fire."""
    assert benchdiff.gate_direction('gen_history_tok_s') == 'higher'
    assert benchdiff.gate_direction('gen_history_ttft_p95') == 'lower'
    assert benchdiff.gate_direction('gen_history_tpot_p95') == 'lower'
    assert benchdiff.gate_direction('gen_history_clean_regressions') is None
    assert benchdiff.gate_direction('gen_history_slow_regressions') is None
    assert benchdiff.gate_direction('gen_history_burn_60s') is None
    assert benchdiff.gate_direction('gen_history_overload_slo_missed') is None
    assert benchdiff.gate_direction('gen_history_shed_requests') is None


def test_emit_baseline_distills_newest_usable_record(tmp_path, records):
    """--emit-baseline (ISSUE 18 satellite): r02 is the newest record
    carrying envelope-source metrics, so its gen_value becomes the tok_s
    baseline — through the SAME extraction code the runtime sentinel
    loads, so gate and sentinel cannot disagree on what a record says."""
    out = tmp_path / 'baseline.json'
    proc = _run(
        records[0], records[1],
        '--emit-baseline', out,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc['schema'] == 'distllm-baseline-envelope/v1'
    assert doc['source'] == 'r02'
    assert doc['metrics']['tok_s'] == {
        'value': 184.18, 'direction': 'higher', 'from_key': 'gen_value',
    }
    # Envelope-only invocations are legal at any record count: a single
    # record emits and exits 0 (nothing to diff), and a pile with no
    # usable metrics emits the EMPTY envelope (the sentinel's counted
    # disarm mode), never a crash.
    solo = _run(records[1], '--emit-baseline', out)
    assert solo.returncode == 0, solo.stdout + solo.stderr
    assert json.loads(out.read_text())['source'] == 'r02'
    empty = _run(records[0], '--emit-baseline', out)
    assert empty.returncode == 0, empty.stdout + empty.stderr
    doc = json.loads(out.read_text())
    assert doc['metrics'] == {} and doc['source'] == ''


def test_gen_kvq_accuracy_regression_trips_gate(tmp_path):
    """A fallen greedy-match fraction alone (tok/s flat) trips the gate:
    the accuracy arm is enforceable, not decorative."""
    prior = {
        'n': 7, 'rc': 0,
        'parsed': {
            'gen_kvq_int8_tok_s': 180.0,
            'gen_kvq_greedy_match': 0.95,
            'gen_kvq_kv_pool_bytes_ratio': 0.502,
        },
    }
    ok_current = {
        'n': 8, 'rc': 0,
        'parsed': {
            'gen_kvq_int8_tok_s': 182.0,
            'gen_kvq_greedy_match': 0.94,  # within --threshold
            'gen_kvq_kv_pool_bytes_ratio': 0.51,
        },
    }
    bad_current = {
        'n': 8, 'rc': 0,
        'parsed': {
            'gen_kvq_int8_tok_s': 181.0,    # throughput fine
            'gen_kvq_greedy_match': 0.40,   # compression got lossier
            'gen_kvq_kv_pool_bytes_ratio': 0.51,
        },
    }
    (tmp_path / 'prior.json').write_text(json.dumps(prior))
    (tmp_path / 'ok.json').write_text(json.dumps(ok_current))
    (tmp_path / 'bad.json').write_text(json.dumps(bad_current))

    proc = _run(tmp_path / 'prior.json', tmp_path / 'ok.json')
    assert proc.returncode == 0, proc.stdout + proc.stderr

    proc = _run(tmp_path / 'prior.json', tmp_path / 'bad.json')
    assert proc.returncode == 1
    assert 'gen_kvq_greedy_match' in proc.stdout


def test_gen_chaos_regression_trips_gate(tmp_path):
    """A CPU-smoke-shaped gen_chaos fragment: dropped recoveries and
    goodput trip the gate; a shed-rate swing alone does not."""
    prior = {
        'n': 7, 'rc': 0,
        'parsed': {
            'gen_chaos_goodput_tokens': 226.0,
            'gen_chaos_recoveries': 2.0,
            'gen_chaos_shed_rate': 0.10,
        },
    }
    ok_current = {
        'n': 8, 'rc': 0,
        'parsed': {
            'gen_chaos_goodput_tokens': 230.0,
            'gen_chaos_recoveries': 2.0,
            'gen_chaos_shed_rate': 0.90,  # informational: never gated
        },
    }
    bad_current = {
        'n': 8, 'rc': 0,
        'parsed': {
            'gen_chaos_goodput_tokens': 150.0,  # -34%
            'gen_chaos_recoveries': 0.0,        # faults stopped surviving
            'gen_chaos_shed_rate': 0.10,
        },
    }
    (tmp_path / 'prior.json').write_text(json.dumps(prior))
    (tmp_path / 'ok.json').write_text(json.dumps(ok_current))
    (tmp_path / 'bad.json').write_text(json.dumps(bad_current))

    proc = _run(tmp_path / 'prior.json', tmp_path / 'ok.json')
    assert proc.returncode == 0, proc.stdout + proc.stderr

    proc = _run(tmp_path / 'prior.json', tmp_path / 'bad.json')
    assert proc.returncode == 1
    assert 'gen_chaos_goodput_tokens' in proc.stdout
    assert 'gen_chaos_recoveries' in proc.stdout
    assert 'gen_chaos_shed_rate' not in proc.stdout.split('regression')[-1]
