"""Tier-1 lint gate: a thin bridge onto the distlint framework.

The rules themselves live in ``distllm_tpu/analysis/`` (see
``docs/static_analysis.md``); this module's job is to keep tier-1
enforcing every one of them. The whole surface is parsed ONCE
(module-scoped project + one ``analyze`` pass feeding all rules — the
legacy version re-parsed the tree per rule, ~8×), then each rule gets
its own test function so a failure names the rule immediately.

ruff / mypy still run when installed (``pip install -e .[lint]``; this
image ships neither and has no egress), configured in pyproject.toml.
"""

from __future__ import annotations

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from distllm_tpu.analysis import (
    META_RULE_IDS,
    RULES,
    analyze,
    iter_rules,
    load_project,
)
from distllm_tpu.analysis.core import SYNTAX_ERROR

REPO = Path(__file__).resolve().parent.parent

# All thirteen registered rules, enforced in tier-1. Pinned by id so a rule
# silently falling out of the registry fails here instead of passing
# vacuously.
EXPECTED_RULES = frozenset(
    {
        'unused-import',
        'raw-print',
        'direct-free',
        'metric-name-catalog',
        'flight-kind-catalog',
        'trace-category-catalog',
        'compile-phase-catalog',
        'step-span-catalog',
        'host-sync-in-hot-path',
        'traced-python-branch',
        'lock-discipline',
        'nondeterminism-in-dispatch',
        'swallowed-exception',
    }
)


@pytest.fixture(scope='module')
def findings() -> dict[str, list]:
    """One parse of the lint surface, one pass of every rule, shared by
    every test below — grouped by rule id (meta rules included)."""
    project = load_project(REPO)
    grouped: dict[str, list] = {
        rule_id: [] for rule_id in (*RULES, *META_RULE_IDS)
    }
    for diag in analyze(project, iter_rules()):
        grouped.setdefault(diag.rule_id, []).append(diag)
    return grouped


def _assert_clean(findings, rule_id: str) -> None:
    diags = findings[rule_id]
    assert not diags, (
        f'[{rule_id}] findings (see docs/static_analysis.md; suppress '
        'only with a justified "# distlint: disable=..." directive):\n'
        + '\n'.join(d.format() for d in diags)
    )


def test_registry_complete():
    assert EXPECTED_RULES == set(RULES), (
        'registered distlint rules drifted from the tier-1 contract'
    )


def test_everything_parses(findings):
    _assert_clean(findings, SYNTAX_ERROR)


@pytest.mark.parametrize('rule_id', sorted(EXPECTED_RULES))
def test_rule_clean(findings, rule_id):
    _assert_clean(findings, rule_id)


@pytest.mark.parametrize(
    'meta_id', [m for m in META_RULE_IDS if m != SYNTAX_ERROR]
)
def test_suppressions_audited(findings, meta_id):
    """Every suppression carries a justification, names a real rule, and
    actually matches a finding (the audit trail cannot rot)."""
    _assert_clean(findings, meta_id)


@pytest.mark.skipif(shutil.which('ruff') is None, reason='ruff not installed')
def test_ruff():
    proc = subprocess.run(
        ['ruff', 'check', 'distllm_tpu', 'tests', 'scripts'],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.skipif(shutil.which('mypy') is None, reason='mypy not installed')
def test_mypy():
    proc = subprocess.run(
        [sys.executable, '-m', 'mypy', 'distllm_tpu'],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
