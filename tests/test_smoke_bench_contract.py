"""Bench driver-contract smoke tests (ISSUE 3 acceptance criteria).

Rounds 3–5 each lost the official record to a timeout because ``bench.py``
printed its single JSON line only after the last stage. These tests pin the
crash-proof contract on CPU with tiny budgets:

- every completed stage is durably checkpointed to ``BENCH_partial.jsonl``
  the moment it finishes;
- killing the orchestrator (SIGTERM — what the driver's ``timeout`` sends)
  while a later stage is mid-flight still emits ONE parseable
  driver-contract line carrying the completed stages' metrics;
- a stage that exceeds its budget is killed without losing earlier stages,
  and the final line is still emitted on normal exit.

The orchestrator subprocess is the real ``python bench.py`` — no test
doubles; ``DISTLLM_BENCH_TEST_HANG_STAGE`` parks the named stage before
its heavy imports so the kill paths run in seconds.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / 'bench.py'


def _bench_env(tmp_path: Path, **extra: str) -> dict[str, str]:
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS='cpu',
        DISTLLM_BENCH_SMALL='1',
        DISTLLM_BENCH_RECORD_DIR=str(tmp_path),
        DISTLLM_BENCH_BUNDLE_DIR=str(tmp_path / 'bundles'),
        DISTLLM_BENCH_WATCHDOG_S='0',
    )
    env.update(extra)
    return env


def _wait_for_stage(partial: Path, stage: str, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if partial.exists() and f'"stage": "{stage}"' in partial.read_text():
            return
        time.sleep(0.5)
    pytest.fail(f'stage {stage!r} never reached {partial}')


def _last_json_line(stdout: str) -> dict:
    lines = [line for line in stdout.strip().splitlines() if line.strip()]
    assert lines, f'no stdout from bench: {stdout!r}'
    return json.loads(lines[-1])


def test_bench_sigterm_mid_stage_still_emits_contract_line(tmp_path):
    """Acceptance criterion: SIGTERM after >= 1 completed stage emits a
    parseable driver-contract line with that stage's metrics, and
    BENCH_partial.jsonl holds every completed stage."""
    partial = tmp_path / 'BENCH_partial.jsonl'
    proc = subprocess.Popen(
        [sys.executable, str(BENCH)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_bench_env(
            tmp_path,
            DISTLLM_BENCH_STAGES='embed,gen',
            DISTLLM_BENCH_TEST_HANG_STAGE='gen',
            DISTLLM_BENCH_DEADLINE_S='600',
        ),
        cwd=REPO,
    )
    try:
        # embed completes and lands on disk while gen hangs mid-flight.
        _wait_for_stage(partial, 'embed', timeout_s=300)
        time.sleep(1)  # let the orchestrator enter the hung gen stage
        proc.send_signal(signal.SIGTERM)
        out, _err = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()

    result = _last_json_line(out)
    # The completed embed stage's metrics survived the kill...
    assert result['metric'] == 'embeddings/sec/chip'
    assert result['value'] > 0
    assert result['unit'] == 'emb/s'
    assert 'embed' in result['stages_completed']
    assert 'gen' not in result['stages_completed']
    assert result['interrupted'] == 'sigterm'
    # ...and the on-disk record holds every completed stage.
    stages = [
        json.loads(line)['stage']
        for line in partial.read_text().splitlines()
        if line.strip()
    ]
    assert 'embed' in stages
    # The composed snapshot tracked the record.
    snapshot = json.loads((tmp_path / 'BENCH_snapshot.json').read_text())
    assert snapshot['value'] == result['value']


def test_bench_stage_timeout_truncates_but_never_zeroes(tmp_path):
    """A stage blowing its budget is killed; earlier stages' metrics and
    the final contract line survive, with the timeout recorded and a
    non-zero exit code — and the backend probe's outcome lands in the
    record (and therefore in the final line)."""
    proc = subprocess.run(
        [sys.executable, str(BENCH)],
        capture_output=True, text=True, timeout=420,
        env=_bench_env(
            tmp_path,
            DISTLLM_BENCH_STAGES='embed,gen',
            DISTLLM_BENCH_TEST_HANG_STAGE='gen',
            DISTLLM_BENCH_DEADLINE_S='600',
            # Per-stage budgets: embed runs for real; the hung gen (parked
            # before its imports by the hang hook) is killed in seconds.
            DISTLLM_BENCH_STAGE_TIMEOUT_S='{"embed": 300, "gen": 3}',
            DISTLLM_BENCH_STAGE_FLOOR_S='1',
        ),
        cwd=REPO,
    )
    # A stage that ended in *_error makes the exit code non-zero; the
    # contract line is printed all the same.
    assert proc.returncode != 0, proc.stderr[-800:]
    result = _last_json_line(proc.stdout)
    assert result['value'] > 0
    assert result['stages_completed'] == ['embed']
    assert 'timed out' in result['gen_error']
    assert 'interrupted' not in result  # normal exit, not a signal
    # The one backend probe is recorded with its outcome.
    assert len(result['probe_attempts']) == 1
    attempt = result['probe_attempts'][0]
    assert attempt['outcome'] == 'ok'
    assert attempt['platform'] == 'cpu'
    assert 'elapsed_s' in attempt
