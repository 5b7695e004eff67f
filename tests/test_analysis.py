"""distlint framework tests: per-rule fixtures + end-to-end self-run.

Every rule gets the four-fixture treatment — a violating snippet, a
clean snippet, a suppressed snippet, and an unused-suppression snippet —
driven through the real driver (:func:`analyze`) on virtual
:class:`SourceFile`\\ s, so suppression application and path scoping are
exercised exactly as in production. The end-to-end tests assert the
repo itself is clean, the CLI exit codes, and the stability of the JSON
output schema.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path
from textwrap import dedent

from distllm_tpu.analysis import (
    RULES,
    Project,
    SourceFile,
    analyze,
    build_report,
)
from distllm_tpu.analysis.core import (
    SUPPRESSION_UNJUSTIFIED,
    SUPPRESSION_UNKNOWN_RULE,
    SUPPRESSION_UNUSED,
    SYNTAX_ERROR,
)
from distllm_tpu.analysis.rules_tpu import TracedIndex

REPO = Path(__file__).resolve().parent.parent
FIXTURE_REL = 'distllm_tpu/_fixture.py'

# A minimal instruments.py stand-in so catalog rules resolve against a
# known catalog instead of the live one.
FAKE_INSTRUMENTS = (
    "REG = None\n"
    "C = REG.counter('distllm_good_total', 'help')\n"
    "FLIGHT_KINDS = frozenset({'decode', 'prefill'})\n"
    "TRACE_EVENT_CATEGORIES = frozenset({'engine'})\n"
    "COMPILE_PHASES = frozenset({'warmup'})\n"
    "STEP_SPANS = frozenset({'plan', 'decode'})\n"
)


def run_rules(
    text: str,
    rule_ids,
    rel: str = FIXTURE_REL,
    *,
    audit: bool = False,
):
    """Analyze one virtual file (plus the fake catalog) with a rule
    subset; returns the diagnostics anchored to the virtual file."""
    files = [
        SourceFile.from_text(
            FAKE_INSTRUMENTS, rel=Project.INSTRUMENTS_REL
        ),
        SourceFile.from_text(dedent(text), rel=rel),
    ]
    project = Project(REPO, files)
    diags = analyze(
        project,
        [RULES[r] for r in rule_ids],
        audit_suppressions=audit,
    )
    return [d for d in diags if d.path == rel]


def rule_ids_of(diags):
    return [d.rule_id for d in diags]


# --------------------------------------------------------------- framework
class TestFramework:
    def test_syntax_error_is_a_diagnostic(self):
        diags = run_rules('def broken(:\n', ['unused-import'])
        assert rule_ids_of(diags) == [SYNTAX_ERROR]

    def test_suppression_same_line(self):
        diags = run_rules(
            'import os  # distlint: disable=unused-import -- doc example\n',
            ['unused-import'],
        )
        assert diags == []

    def test_suppression_standalone_comment_covers_next_line(self):
        diags = run_rules(
            '# distlint: disable=unused-import -- doc example\n'
            'import os\n',
            ['unused-import'],
        )
        assert diags == []

    def test_suppression_inside_string_literal_is_inert(self):
        diags = run_rules(
            'X = "import os  # distlint: disable=unused-import -- no"\n'
            'import os\n',
            ['unused-import'],
        )
        assert rule_ids_of(diags) == ['unused-import']

    def test_unjustified_suppression_flagged(self):
        diags = run_rules(
            'import os  # distlint: disable=unused-import\n',
            ['unused-import'],
            audit=True,
        )
        # The finding is suppressed, but the naked directive is flagged.
        assert rule_ids_of(diags) == [SUPPRESSION_UNJUSTIFIED]

    def test_unused_suppression_flagged(self):
        diags = run_rules(
            'import os\n'
            'x = os.sep  # distlint: disable=unused-import -- stale\n',
            ['unused-import'],
            audit=True,
        )
        assert rule_ids_of(diags) == [SUPPRESSION_UNUSED]

    def test_unknown_rule_suppression_flagged(self):
        diags = run_rules(
            'x = 1  # distlint: disable=no-such-rule -- typo\n',
            ['unused-import'],
            audit=True,
        )
        assert SUPPRESSION_UNKNOWN_RULE in rule_ids_of(diags)

    def test_meta_rule_suppression_flagged(self):
        """disable=<meta-rule> can never work (meta rules are
        unsuppressible) — the dead directive must be flagged, not
        accumulate silently outside both the match and unused audits."""
        diags = run_rules(
            'x = 1  # distlint: disable=suppression-unused -- futile\n',
            ['unused-import'],
            audit=True,
        )
        assert rule_ids_of(diags) == [SUPPRESSION_UNKNOWN_RULE]
        assert 'not suppressible' in diags[0].message

    def test_multi_rule_suppression(self):
        diags = run_rules(
            'import os  # distlint: disable=unused-import,raw-print -- both\n',
            ['unused-import'],
        )
        assert diags == []


# ------------------------------------------------------------ hygiene rules
class TestUnusedImport:
    def test_violation(self):
        diags = run_rules('import os\n', ['unused-import'])
        assert rule_ids_of(diags) == ['unused-import']
        assert diags[0].line == 1

    def test_clean(self):
        assert run_rules('import os\nX = os.sep\n', ['unused-import']) == []

    def test_noqa_exempts(self):
        text = 'import os  # noqa: F401\n'
        assert run_rules(text, ['unused-import']) == []

    def test_init_py_out_of_scope(self):
        diags = run_rules(
            'import os\n', ['unused-import'],
            rel='distllm_tpu/sub/__init__.py',
        )
        assert diags == []


class TestRawPrint:
    def test_violation(self):
        diags = run_rules("print('hello')\n", ['raw-print'])
        assert rule_ids_of(diags) == ['raw-print']

    def test_clean(self):
        assert run_rules("log_event('hello')\n", ['raw-print']) == []

    def test_observability_exempt(self):
        diags = run_rules(
            "print('x')\n", ['raw-print'],
            rel='distllm_tpu/observability/metrics.py',
        )
        assert diags == []

    def test_suppressed(self):
        diags = run_rules(
            "print('x')  # distlint: disable=raw-print -- CLI output\n",
            ['raw-print'],
        )
        assert diags == []


class TestDirectFree:
    def test_violation(self):
        diags = run_rules('def f(a):\n    a.free(1)\n', ['direct-free'])
        assert rule_ids_of(diags) == ['direct-free']

    def test_allocator_module_exempt(self):
        diags = run_rules(
            'def f(a):\n    a.free(1)\n', ['direct-free'],
            rel='distllm_tpu/generate/engine/kv_cache.py',
        )
        assert diags == []


ENGINE_REL = 'distllm_tpu/generate/engine/_fixture.py'


class TestSwallowedException:
    """swallowed-exception: in engine/server/tier/resilience paths, an
    ``except`` must re-raise or emit telemetry (ISSUE 15)."""

    def test_silent_pass_flagged(self):
        diags = run_rules(
            'def f(x):\n'
            '    try:\n'
            '        x.go()\n'
            '    except ValueError:\n'
            '        pass\n',
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert rule_ids_of(diags) == ['swallowed-exception']
        assert diags[0].line == 4

    def test_silent_return_flagged(self):
        diags = run_rules(
            'def f(x):\n'
            '    try:\n'
            '        return x.go()\n'
            '    except Exception:\n'
            '        return None\n',
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert rule_ids_of(diags) == ['swallowed-exception']

    def test_reraise_clean(self):
        diags = run_rules(
            'def f(x):\n'
            '    try:\n'
            '        x.go()\n'
            '    except ValueError:\n'
            '        raise RuntimeError("context")\n',
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert diags == []

    def test_metric_emission_clean(self):
        diags = run_rules(
            'def f(x, m):\n'
            '    try:\n'
            '        x.go()\n'
            '    except ValueError:\n'
            "        m.labels(tier='disk').inc()\n",
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert diags == []

    def test_log_event_clean(self):
        diags = run_rules(
            'def f(x):\n'
            '    try:\n'
            '        x.go()\n'
            '    except ValueError as exc:\n'
            '        log_event(f"failed: {exc}")\n',
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert diags == []

    def test_flight_record_clean(self):
        diags = run_rules(
            'def f(self, x):\n'
            '    try:\n'
            '        x.go()\n'
            '    except ValueError as exc:\n'
            "        self.flight.record('event', error=repr(exc))\n",
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert diags == []

    def test_telemetry_note_clean(self):
        diags = run_rules(
            'def f(self, x):\n'
            '    try:\n'
            '        x.go()\n'
            '    except ValueError as exc:\n'
            "        self.telemetry['fallback'] = repr(exc)\n",
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert diags == []

    def test_out_of_scope_path_exempt(self):
        # The rule is scoped to serving-critical paths; ordinary library
        # modules keep their idioms.
        diags = run_rules(
            'def f(x):\n'
            '    try:\n'
            '        x.go()\n'
            '    except ValueError:\n'
            '        pass\n',
            ['swallowed-exception'],
        )
        assert diags == []

    def test_suppressed(self):
        diags = run_rules(
            'def f(x):\n'
            '    try:\n'
            '        x.go()\n'
            '    # distlint: disable=swallowed-exception -- membership probe\n'
            '    except ValueError:\n'
            '        pass\n',
            ['swallowed-exception'],
            rel=ENGINE_REL,
        )
        assert diags == []

    def test_unused_suppression_flagged(self):
        diags = run_rules(
            'def f(x):\n'
            '    try:\n'
            '        x.go()\n'
            '    # distlint: disable=swallowed-exception -- stale\n'
            '    except ValueError:\n'
            '        raise\n',
            ['swallowed-exception'],
            rel=ENGINE_REL,
            audit=True,
        )
        assert rule_ids_of(diags) == [SUPPRESSION_UNUSED]


# ------------------------------------------------------------ catalog rules
class TestMetricNameCatalog:
    def test_adhoc_registration_flagged(self):
        diags = run_rules(
            "def f(reg):\n    return reg.counter('distllm_rogue_total')\n",
            ['metric-name-catalog'],
        )
        assert rule_ids_of(diags) == ['metric-name-catalog']

    def test_docstring_reference_flagged(self):
        diags = run_rules(
            '"""Reports distllm_phantom_total per window."""\n',
            ['metric-name-catalog'],
        )
        assert rule_ids_of(diags) == ['metric-name-catalog']

    def test_registered_name_clean(self):
        diags = run_rules(
            '"""Reports distllm_good_total per window."""\n'
            "def f(reg):\n    return reg.counter('distllm_good_total')\n",
            ['metric-name-catalog'],
        )
        assert diags == []

    def test_exposition_suffix_clean(self):
        diags = run_rules(
            '"""See distllm_good_total_bucket in the scrape."""\n',
            ['metric-name-catalog'],
        )
        assert diags == []

    def test_named_constant_registration_flagged(self):
        """A metric registered through a module string constant is a
        registration context too — the legacy everywhere-scan caught the
        literal at its definition site, and the scoped rule must not let
        `counter(_NAME)` reopen silent series drift."""
        diags = run_rules(
            "_NAME = 'distllm_rogue_total'\n"
            'def f(reg):\n    return reg.counter(_NAME)\n',
            ['metric-name-catalog'],
        )
        assert rule_ids_of(diags) == ['metric-name-catalog']

    def test_annotated_constant_registration_flagged(self):
        """`_NAME: Final = '...'` binds the same way — AnnAssign must
        not slip past the named-constant resolution."""
        diags = run_rules(
            'from typing import Final\n'
            "_NAME: Final = 'distllm_rogue_total'\n"
            'def f(reg):\n    return reg.counter(_NAME)\n',
            ['metric-name-catalog'],
        )
        assert rule_ids_of(diags) == ['metric-name-catalog']

    def test_named_constant_registration_clean_when_cataloged(self):
        diags = run_rules(
            "_NAME = 'distllm_good_total'\n"
            'def f(reg):\n    return reg.counter(_NAME)\n',
            ['metric-name-catalog'],
        )
        assert diags == []

    def test_instruments_docstring_typo_flagged(self):
        """instruments.py registration CALLS are the catalog (exempt),
        but its docstrings still document series and must not drift —
        the legacy everywhere-scan covered them."""
        files = [
            SourceFile.from_text(
                '"""Catalog. Reports distllm_phantom_total."""\n'
                + FAKE_INSTRUMENTS,
                rel=Project.INSTRUMENTS_REL,
            ),
        ]
        diags = analyze(
            Project(REPO, files), [RULES['metric-name-catalog']],
            audit_suppressions=False,
        )
        assert rule_ids_of(diags) == ['metric-name-catalog']
        assert 'distllm_phantom_total' in diags[0].message

    def test_contextvar_identifier_not_flagged(self):
        """The PR 7 workaround class: an identifier-shaped string OUTSIDE
        registration/exposition contexts is not a metric reference."""
        diags = run_rules(
            'import contextvars\n'
            "V = contextvars.ContextVar('distllm_request_id', default=None)\n",
            ['metric-name-catalog'],
        )
        assert diags == []


class TestFlightKindCatalog:
    def test_violation(self):
        diags = run_rules(
            "def f(rec):\n    rec.record('rogue', x=1)\n",
            ['flight-kind-catalog'],
        )
        assert rule_ids_of(diags) == ['flight-kind-catalog']

    def test_ifexp_branches_checked(self):
        diags = run_rules(
            "def f(rec, m):\n"
            "    rec.record('decode' if m else 'rogue')\n",
            ['flight-kind-catalog'],
        )
        assert rule_ids_of(diags) == ['flight-kind-catalog']

    def test_clean(self):
        diags = run_rules(
            "def f(rec):\n    rec.record('decode', x=1)\n",
            ['flight-kind-catalog'],
        )
        assert diags == []


class TestTraceCategoryCatalog:
    def test_kwarg_violation(self):
        diags = run_rules(
            "def f(emit):\n    emit(cat='rogue')\n",
            ['trace-category-catalog'],
        )
        assert rule_ids_of(diags) == ['trace-category-catalog']

    def test_dict_key_violation(self):
        diags = run_rules(
            "EVENT = {'cat': 'rogue', 'ph': 'X'}\n",
            ['trace-category-catalog'],
        )
        assert rule_ids_of(diags) == ['trace-category-catalog']

    def test_clean(self):
        diags = run_rules(
            "EVENT = {'cat': 'engine'}\n"
            "def f(emit):\n    emit(cat='engine')\n",
            ['trace-category-catalog'],
        )
        assert diags == []


class TestCompilePhaseCatalog:
    def test_violation(self):
        diags = run_rules(
            "def f(w):\n    with w.phase('rogue', 'shape'):\n        pass\n",
            ['compile-phase-catalog'],
        )
        assert rule_ids_of(diags) == ['compile-phase-catalog']

    def test_clean(self):
        diags = run_rules(
            "def f(w):\n    with w.phase('warmup', 'shape'):\n        pass\n",
            ['compile-phase-catalog'],
        )
        assert diags == []


class TestStepSpanCatalog:
    def test_violation(self):
        diags = run_rules(
            "def f(step, k):\n"
            "    step.mark('rogue')\n"
            "    step.mark('decode' if k else 'mixed')\n"
            "    with step.inside('also_rogue'):\n        pass\n",
            ['step-span-catalog'],
        )
        assert rule_ids_of(diags) == ['step-span-catalog'] * 3

    def test_clean(self):
        diags = run_rules(
            "def f(step, k, name):\n"
            "    step.mark('plan')\n"
            "    step.mark('decode' if k else 'plan')\n"
            "    step.mark(name)\n",
            ['step-span-catalog'],
        )
        assert diags == []


# ---------------------------------------------------------------- TPU rules
HOT_PREAMBLE = 'import numpy as np\nimport jax.numpy as jnp\n'


class TestHostSyncInHotPath:
    def test_stale_hot_paths_entry_flagged(self):
        """A renamed engine/model function must not silently shrink the
        hot-path surface: every HOT_PATHS qualname is audited against
        the source it names."""
        engine_rel = 'distllm_tpu/generate/engine/engine.py'
        files = [
            SourceFile.from_text(
                'class LLMEngine:\n    def step(self):\n        pass\n',
                rel=engine_rel,
            ),
        ]
        diags = analyze(Project(REPO, files), [RULES['host-sync-in-hot-path']])
        stale = [d for d in diags if 'HOT_PATHS entry' in d.message]
        # Every listed engine qualname except LLMEngine.step is missing
        # from the stub; mistral.py is not in this project -> skipped.
        from distllm_tpu.analysis.rules_tpu import HostSyncInHotPathRule
        expected = len(HostSyncInHotPathRule.HOT_PATHS[engine_rel]) - 1
        assert len(stale) == expected
        assert all(d.path == engine_rel for d in stale)

    def test_hot_paths_entries_resolve_in_repo(self):
        """The shipped HOT_PATHS table matches today's source (the
        self-run also proves this, but pin it directly)."""
        from distllm_tpu.analysis.core import load_project
        from distllm_tpu.analysis.rules_tpu import HostSyncInHotPathRule
        rule = HostSyncInHotPathRule.__new__(HostSyncInHotPathRule)
        paths = [REPO / rel for rel in HostSyncInHotPathRule.HOT_PATHS]
        project = load_project(REPO, paths)
        assert list(rule.check_project(project)) == []

    def test_violations(self):
        diags = run_rules(
            HOT_PREAMBLE
            + 'def loop(self):  # distlint: hot-path\n'
            '    toks = self._decode_window(1)\n'
            '    a = np.asarray(toks)\n'
            '    b = toks.item()\n'
            '    c = toks.tolist()\n'
            '    d = int(toks)\n'
            '    toks.block_until_ready()\n'
            '    return a, b, c, d\n',
            ['host-sync-in-hot-path'],
        )
        assert rule_ids_of(diags) == ['host-sync-in-hot-path'] * 5

    def test_clean_host_only_math(self):
        diags = run_rules(
            HOT_PREAMBLE
            + 'def loop(self, lengths):  # distlint: hot-path\n'
            '    total = int(lengths.sum())\n'
            '    ids = np.zeros((4,), np.int32)\n'
            '    return total, ids\n',
            ['host-sync-in-hot-path'],
        )
        assert diags == []

    def test_host_copy_ends_tracking(self):
        """int() of an np.asarray result is free — the sync was already
        charged to the asarray (which needs its own suppression)."""
        diags = run_rules(
            HOT_PREAMBLE
            + 'def loop(self):  # distlint: hot-path\n'
            '    toks = self._decode_window(1)\n'
            '    # distlint: disable=host-sync-in-hot-path -- designed fetch point\n'
            '    host = np.asarray(toks)\n'
            '    return int(host[0])\n',
            ['host-sync-in-hot-path'],
        )
        assert diags == []

    def test_method_sync_on_host_copy_free(self):
        """.tolist()/.item() of the fetched numpy copy is free — the
        sync was already charged (and suppressed) at the asarray; the
        same methods on a device value or an unknown receiver stay
        flagged."""
        diags = run_rules(
            HOT_PREAMBLE
            + 'def loop(self):  # distlint: hot-path\n'
            '    toks = self._decode_window(1)\n'
            '    # distlint: disable=host-sync-in-hot-path -- designed fetch point\n'
            '    host = np.asarray(toks)\n'
            '    ids = host.tolist()\n'
            '    first = host[0].item()\n'
            '    bad = toks.tolist()\n'
            '    unknown = self.window.tolist()\n'
            '    return ids, first, bad, unknown\n',
            ['host-sync-in-hot-path'],
        )
        # Only the device receiver (toks) and the untracked receiver
        # (self.window) are flagged.
        assert [d.line for d in diags] == [9, 10]

    def test_not_hot_function_ignored(self):
        diags = run_rules(
            HOT_PREAMBLE
            + 'def warmup(self):\n'
            '    toks = self._decode_window(1)\n'
            '    return np.asarray(toks)\n',
            ['host-sync-in-hot-path'],
        )
        assert diags == []

    def test_builtin_hot_paths_cover_engine_window_loop(self):
        from distllm_tpu.analysis.rules_tpu import HostSyncInHotPathRule

        rule = HostSyncInHotPathRule()
        engine_rel = 'distllm_tpu/generate/engine/engine.py'
        assert 'LLMEngine._dispatch_window' in rule.HOT_PATHS[engine_rel]
        src = SourceFile.from_path(REPO / engine_rel, REPO)
        hot = {q for q, _ in rule._hot_functions(src)}
        assert 'LLMEngine._dispatch_window' in hot
        # process_one moved with the loop body when _run_to_completion
        # grew its crash-domain recovery wrapper (ISSUE 15).
        assert 'LLMEngine._serve_pipelined.<locals>.process_one' in hot


class TestTracedPythonBranch:
    def test_if_on_traced_value(self):
        diags = run_rules(
            'import jax\nimport jax.numpy as jnp\n'
            '@jax.jit\n'
            'def f(x):\n'
            '    s = jnp.sum(x)\n'
            '    if s > 0:\n'
            '        return s\n'
            '    return -s\n',
            ['traced-python-branch'],
        )
        assert rule_ids_of(diags) == ['traced-python-branch']

    def test_while_and_assert(self):
        diags = run_rules(
            'import jax\nimport jax.numpy as jnp\n'
            '@jax.jit\n'
            'def f(x):\n'
            '    s = jnp.sum(x)\n'
            '    assert s > 0\n'
            '    while s < 10:\n'
            '        s = s + 1\n'
            '    return s\n',
            ['traced-python-branch'],
        )
        assert rule_ids_of(diags) == ['traced-python-branch'] * 2

    def test_shape_branch_is_static_and_clean(self):
        diags = run_rules(
            'import jax\nimport jax.numpy as jnp\n'
            '@jax.jit\n'
            'def f(x):\n'
            '    y = jnp.pad(x, 2)\n'
            '    m, k = y.shape\n'
            '    if m > k:\n'
            '        return y\n'
            '    return y.T\n',
            ['traced-python-branch'],
        )
        assert diags == []

    def test_untraced_function_clean(self):
        diags = run_rules(
            'import jax.numpy as jnp\n'
            'def host_helper(x):\n'
            '    s = jnp.sum(x)\n'
            '    if s > 0:\n'
            '        return s\n'
            '    return -s\n',
            ['traced-python-branch'],
        )
        assert diags == []

    def test_identity_test_is_static_and_clean(self):
        # ``q_lens is None`` (ops/paged_attention.py, an absent operand's
        # default) reads which object the name is bound to, even where the
        # same name is later rebound from a device expression; a VALUE test
        # of that name is still a finding.
        source = (
            'import jax\nimport jax.numpy as jnp\n'
            '@jax.jit\n'
            'def f(x, lens=None):\n'
            '    if lens is None:\n'
            '        lens = jnp.full((2,), 3)\n'
            '    if lens is not None and x is not None:\n'
            '        x = x + lens\n'
            '{}'
            '    return x\n'
        )
        assert run_rules(source.format(''), ['traced-python-branch']) == []
        diags = run_rules(
            source.format('    if lens:\n        x = x * 2\n'),
            ['traced-python-branch'],
        )
        assert rule_ids_of(diags) == ['traced-python-branch']

    def test_isinstance_dispatch_is_static_and_clean(self):
        # The QuantizedKV-vs-bare-array pytree dispatch idiom
        # (ops/paged_attention.py write paths): isinstance inspects the
        # container's Python type at trace time — never a traced value —
        # even when the SAME name is later rebound from a device
        # expression (the flow-insensitive fixpoint must not leak that
        # back into the isinstance test).
        diags = run_rules(
            'import jax\nimport jax.numpy as jnp\n'
            '@jax.jit\n'
            'def f(cache, new):\n'
            '    if isinstance(cache, tuple):\n'
            '        return cache\n'
            '    cache = cache + jnp.sum(new)\n'
            '    return cache\n',
            ['traced-python-branch'],
        )
        assert diags == []

    def test_isinstance_bound_flag_is_static_and_clean(self):
        # `quantized = isinstance(...)` is a static bool, not a
        # device-derived value — branching on it later stays clean
        # (engine._write_prefill_all_layers).
        diags = run_rules(
            'import jax\nimport jax.numpy as jnp\n'
            '@jax.jit\n'
            'def f(cache, new):\n'
            '    cache = cache + jnp.sum(new)\n'
            '    quantized = isinstance(cache, tuple)\n'
            '    if quantized:\n'
            '        return cache\n'
            '    return -cache\n',
            ['traced-python-branch'],
        )
        assert diags == []

    def test_closure_reaches_scan_body(self):
        diags = run_rules(
            'import jax\nimport jax.numpy as jnp\n'
            'from jax import lax\n'
            'def layer(c, x):\n'
            '    s = jnp.sum(x)\n'
            '    if s > 0:\n'
            '        return c, x\n'
            '    return c, -x\n'
            '@jax.jit\n'
            'def f(xs):\n'
            '    return lax.scan(layer, 0, xs)\n',
            ['traced-python-branch'],
        )
        assert rule_ids_of(diags) == ['traced-python-branch']


class TestNondeterminismInDispatch:
    def test_time_in_traced(self):
        diags = run_rules(
            'import jax\nimport time\n'
            '@jax.jit\n'
            'def f(x):\n'
            '    return x + time.time()\n',
            ['nondeterminism-in-dispatch'],
        )
        assert rule_ids_of(diags) == ['nondeterminism-in-dispatch']

    def test_np_random_in_traced(self):
        diags = run_rules(
            'import jax\nimport numpy as np\n'
            '@jax.jit\n'
            'def f(x):\n'
            '    return x + np.random.rand()\n',
            ['nondeterminism-in-dispatch'],
        )
        assert rule_ids_of(diags) == ['nondeterminism-in-dispatch']

    def test_jax_random_clean(self):
        diags = run_rules(
            'import jax\n'
            '@jax.jit\n'
            'def f(x, key):\n'
            '    return x + jax.random.normal(key, x.shape)\n',
            ['nondeterminism-in-dispatch'],
        )
        assert diags == []

    def test_host_function_clean(self):
        diags = run_rules(
            'import time\n'
            'def budget():\n'
            '    return time.monotonic()\n',
            ['nondeterminism-in-dispatch'],
        )
        assert diags == []


LOCK_PREAMBLE = (
    'import threading\n'
    'class C:\n'
    '    def __init__(self):\n'
    '        self._lock = threading.Lock()\n'
    '        self._items = []  # guarded by self._lock\n'
)


class TestLockDiscipline:
    def test_unlocked_read_flagged(self):
        diags = run_rules(
            LOCK_PREAMBLE
            + '    def peek(self):\n'
            '        return len(self._items)\n',
            ['lock-discipline'],
        )
        assert rule_ids_of(diags) == ['lock-discipline']

    def test_locked_access_clean(self):
        diags = run_rules(
            LOCK_PREAMBLE
            + '    def add(self, x):\n'
            '        with self._lock:\n'
            '            self._items.append(x)\n',
            ['lock-discipline'],
        )
        assert diags == []

    def test_holds_lock_def_annotation(self):
        diags = run_rules(
            LOCK_PREAMBLE
            + '    def _drain_locked(self):  # guarded by self._lock\n'
            '        out = list(self._items)\n'
            '        self._items.clear()\n'
            '        return out\n',
            ['lock-discipline'],
        )
        assert diags == []

    def test_unlocked_write_flagged(self):
        diags = run_rules(
            LOCK_PREAMBLE
            + '    def reset(self):\n'
            '        self._items = []\n',
            ['lock-discipline'],
        )
        assert rule_ids_of(diags) == ['lock-discipline']

    def test_annotation_inside_hot_method_does_not_exempt_it(self):
        """An annotated assignment in a non-constructor method exempts
        NOTHING — not even its own line. Letting the annotation silence
        the finding would be an unaudited suppression channel (annotate
        the racy write and the detector goes quiet exactly there); the
        only sanctioned escape is a justified `# distlint: disable`."""
        diags = run_rules(
            'import threading\n'
            'class C:\n'
            '    def __init__(self):\n'
            '        self._lock = threading.Lock()\n'
            '    def reset(self):\n'
            '        self._items = []  # guarded by self._lock\n'
            '        return len(self._items)\n',
            ['lock-discipline'],
        )
        # Both the annotated unlocked write (line 6) and the unlocked
        # read (line 7) are races.
        assert rule_ids_of(diags) == ['lock-discipline', 'lock-discipline']
        assert [d.line for d in diags] == [6, 7]

    def test_closure_under_lock_not_blessed(self):
        """A callback DEFINED inside `with self._lock:` executes later,
        without the lock — the watchdog-timer race class the rule was
        built for. Line containment must not bless its body."""
        diags = run_rules(
            'import threading\n'
            'class C:\n'
            '    def __init__(self):\n'
            '        self._lock = threading.Lock()\n'
            '        self._active = {}  # guarded by self._lock\n'
            '    def arm(self):\n'
            '        with self._lock:\n'
            '            cb = lambda: self._active.pop(1)\n'
            '            self._timer = threading.Timer(1.0, cb)\n'
            '    def sync_use(self):\n'
            '        with self._lock:\n'
            '            return len(self._active)\n',
            ['lock-discipline'],
        )
        assert rule_ids_of(diags) == ['lock-discipline']
        assert diags[0].line == 8

    def test_annotated_write_under_lock_is_clean(self):
        diags = run_rules(
            'import threading\n'
            'class C:\n'
            '    def __init__(self):\n'
            '        self._lock = threading.Lock()\n'
            '    def reset(self):\n'
            '        with self._lock:\n'
            '            self._items = []  # guarded by self._lock\n',
            ['lock-discipline'],
        )
        assert diags == []

    def test_unannotated_class_ignored(self):
        diags = run_rules(
            'import threading\n'
            'class C:\n'
            '    def __init__(self):\n'
            '        self._lock = threading.Lock()\n'
            '        self._items = []\n'
            '    def peek(self):\n'
            '        return len(self._items)\n',
            ['lock-discipline'],
        )
        assert diags == []


# ----------------------------------------------------- traced-index details
class TestTracedIndex:
    def test_partial_wrapped_pallas_kernel_detected(self):
        src = SourceFile.from_text(
            dedent(
                '''
                import functools
                import jax
                from jax.experimental import pallas as pl
                def _kernel(x_ref, o_ref, *, steps):
                    o_ref[...] = x_ref[...]
                def op(x):
                    return pl.pallas_call(
                        functools.partial(_kernel, steps=2),
                        out_shape=None,
                    )(x)
                '''
            ),
            rel=FIXTURE_REL,
        )
        index = TracedIndex(src)
        assert '_kernel' in index.traced

    def test_partial_bound_on_own_line_detected(self):
        # The repo's real kernels bind the partial to a name first
        # (ops/paged_attention.py) — the wrap-site scan must resolve
        # that alias or the hottest traced code goes unlinted.
        src = SourceFile.from_text(
            dedent(
                '''
                import functools
                from jax.experimental import pallas as pl
                def _kernel(x_ref, o_ref, *, steps):
                    o_ref[...] = x_ref[...]
                def op(x):
                    kernel = functools.partial(_kernel, steps=2)
                    return pl.pallas_call(kernel, out_shape=None)(x)
                '''
            ),
            rel=FIXTURE_REL,
        )
        index = TracedIndex(src)
        assert '_kernel' in index.traced

    def test_control_flow_function_operands_seeded(self):
        """while_loop/fori_loop bodies and cond/switch branches are the
        traced code — they sit past args[0], so the wrap-site scan must
        look at every function-valued operand."""
        src = SourceFile.from_text(
            dedent(
                '''
                from jax import lax
                def _pred(s):
                    return s[0]
                def _body(s):
                    return s
                def _tf(x):
                    return x
                def _ff(x):
                    return x
                def _b0(x):
                    return x
                def _b1(x):
                    return x
                def op(x):
                    y = lax.while_loop(_pred, _body, x)
                    z = lax.cond(True, _tf, _ff, y)
                    w = lax.fori_loop(0, 3, _body, z)
                    return lax.switch(0, [_b0, _b1], w)
                '''
            ),
            rel=FIXTURE_REL,
        )
        index = TracedIndex(src)
        for expected in ('_pred', '_body', '_tf', '_ff', '_b0', '_b1'):
            assert expected in index.traced, f'{expected} not traced'

    def test_marker_seeds_tracing(self):
        src = SourceFile.from_text(
            'def dispatch(x):  # distlint: traced\n'
            '    return helper(x)\n'
            'def helper(x):\n'
            '    return x\n',
            rel=FIXTURE_REL,
        )
        index = TracedIndex(src)
        assert {'dispatch', 'helper'} <= index.traced

    def test_model_dispatch_surface_is_traced(self):
        """The cross-module-jitted model entry points carry markers, and
        the closure reaches their layer bodies."""
        src = SourceFile.from_path(
            REPO / 'distllm_tpu/models/mistral.py', REPO
        )
        index = TracedIndex(src)
        for expected in ('mixed_window', 'spec_window', 'decode_step',
                         'prefill_paged', '_forward'):
            assert any(
                q == expected or q.endswith('.' + expected)
                for q in index.traced
            ), f'{expected} not traced'

    def test_kv_write_and_kernel_surface_is_traced(self):
        """The paged-attention Pallas kernel (partial bound on its own
        line) and the cross-module KV-write helpers are all visible to
        the traced rules."""
        src = SourceFile.from_path(
            REPO / 'distllm_tpu/ops/paged_attention.py', REPO
        )
        index = TracedIndex(src)
        for expected in ('_ragged_paged_attn_kernel', 'write_token_kv',
                         'write_chunk_kv', 'write_prefill_kv'):
            assert expected in index.traced, f'{expected} not traced'
        mix = SourceFile.from_path(
            REPO / 'distllm_tpu/models/mixtral.py', REPO
        )
        assert 'moe_mlp' in TracedIndex(mix).traced


# ------------------------------------------------------------- end to end
class TestEndToEnd:
    def test_repo_is_clean(self):
        report = build_report(REPO)
        assert report['summary']['total'] == 0, json.dumps(
            report['diagnostics'], indent=2
        )

    def test_json_schema_stable(self):
        report = build_report(REPO)
        assert report['version'] == 1
        assert sorted(report) == [
            'diagnostics', 'files_analyzed', 'root', 'rules', 'summary',
            'version',
        ]
        assert report['files_analyzed'] > 100
        assert sorted(report['summary']) == ['by_rule', 'total']
        rule_entry = report['rules'][0]
        assert sorted(rule_entry) == ['description', 'id', 'severity']

    def test_json_diagnostic_schema(self, tmp_path):
        # A root with its own tiny catalog and one dirty file: exercises
        # the CLI subprocess, the nonzero exit, and the diagnostic keys.
        pkg = tmp_path / 'distllm_tpu'
        (pkg / 'observability').mkdir(parents=True)
        (pkg / 'observability' / 'instruments.py').write_text(
            FAKE_INSTRUMENTS
        )
        (pkg / 'bad.py').write_text('import os\nprint("hi")\n')
        proc = subprocess.run(
            [
                sys.executable, str(REPO / 'scripts' / 'distlint.py'),
                '--root', str(tmp_path), '--json',
            ],
            capture_output=True, text=True,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report['summary']['total'] == 2
        assert sorted(report['summary']['by_rule']) == [
            'raw-print', 'unused-import',
        ]
        for diag in report['diagnostics']:
            assert sorted(diag) == [
                'line', 'message', 'path', 'rule_id', 'severity',
            ]
            assert diag['path'] == 'distllm_tpu/bad.py'

    def test_cli_exit_zero_on_clean_repo(self):
        proc = subprocess.run(
            [sys.executable, str(REPO / 'scripts' / 'distlint.py')],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert 'clean' in proc.stdout

    def test_cli_list_rules(self):
        proc = subprocess.run(
            [
                sys.executable, str(REPO / 'scripts' / 'distlint.py'),
                '--list-rules',
            ],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0
        for rule_id in RULES:
            assert rule_id in proc.stdout

    def test_cli_rule_subset(self):
        proc = subprocess.run(
            [
                sys.executable, str(REPO / 'scripts' / 'distlint.py'),
                '--rules', 'raw-print',
            ],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_cli_unknown_rule_errors(self):
        proc = subprocess.run(
            [
                sys.executable, str(REPO / 'scripts' / 'distlint.py'),
                '--rules', 'no-such-rule',
            ],
            capture_output=True, text=True, cwd=REPO,
        )
        assert proc.returncode == 2

    def test_single_parse_per_file(self, monkeypatch):
        """The driver parses each file exactly once regardless of how
        many rules run (the legacy gate re-parsed per rule, ~8×)."""
        import ast as ast_module

        calls: list[str] = []
        real_parse = ast_module.parse

        def counting_parse(source, filename='<unknown>', *a, **k):
            calls.append(str(filename))
            return real_parse(source, filename, *a, **k)

        monkeypatch.setattr(ast_module, 'parse', counting_parse)
        run_rules('X = 1\n', sorted(RULES))
        fixture_parses = [c for c in calls if c == FIXTURE_REL]
        assert len(fixture_parses) == 1
