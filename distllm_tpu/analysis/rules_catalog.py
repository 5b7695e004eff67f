"""Catalog rules: every schema-bearing name the package emits must be
registered in the ``instruments.py`` catalogs.

Four rules, one per catalog: metric names, FlightRecorder kinds, trace
event categories, compile-phase kinds. A name minted at a call site would
silently fragment the schema that scrapes, debug bundles,
``aggregate.py``, and the Perfetto exporter replay — the catalog is the
contract, so the analyzer treats an uncatalogued name as an error.

The metric-name rule is *scoped* (the one behavioral change vs. the
legacy walk): it checks registration contexts — ``*.counter(...)`` /
``*.gauge(...)`` / ``*.histogram(...)`` call sites — and docstrings
(which double as operator documentation), not every string constant in
the package. The legacy everywhere-scan forced PR 7 to rename a
ContextVar to ``distllm-request-id`` purely because its natural
identifier spelling matched the metric-name regex; identifiers that are
not metrics no longer dictate naming.
"""

from __future__ import annotations

import ast
import re

from distllm_tpu.analysis.core import (
    Diagnostic,
    Project,
    Rule,
    SourceFile,
    register,
)

_METRIC_NAME_RE = re.compile(r'^distllm_[a-z0-9_]+$')
_EXPOSITION_SUFFIX_RE = re.compile(r'_(bucket|sum|count)$')
_WORD_RE = re.compile(r'[A-Za-z0-9_]+')


class _CatalogRule(Rule):
    """Shared plumbing: package scope + a "catalog parsed non-empty"
    project check (an empty catalog means the rule is broken, which must
    fail loudly rather than pass vacuously)."""

    catalog_label = ''

    def applies(self, source: SourceFile) -> bool:
        return self.in_package(source)

    def catalog(self, project: Project) -> frozenset[str]:
        raise NotImplementedError

    def check_project(self, project: Project):
        if not self.catalog(project):
            yield Diagnostic(
                rule_id=self.id,
                path=Project.INSTRUMENTS_REL,
                line=1,
                message=(
                    f'{self.catalog_label} catalog parse came back empty '
                    '— the rule is broken or instruments.py moved'
                ),
            )


def _docstrings(source):
    """Yield ``(lineno, text)`` for every docstring constant."""
    scopes = [
        node
        for node in (source.tree, *source.nodes())
        if isinstance(
            node,
            (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef),
        )
    ]
    seen = set()
    for scope in scopes:
        if id(scope) in seen:
            continue
        seen.add(id(scope))
        body = getattr(scope, 'body', [])
        if (
            body
            and isinstance(body[0], ast.Expr)
            and isinstance(body[0].value, ast.Constant)
            and isinstance(body[0].value.value, str)
        ):
            yield body[0].value.lineno, body[0].value.value


@register
class MetricNameCatalogRule(_CatalogRule):
    """Metric names are registered in the instruments.py catalog.

    Checked contexts: the first string argument of every
    ``*.counter/gauge/histogram(...)`` call (an ad-hoc registration would
    create a series the catalog and the first-scrape-full-schema guarantee
    know nothing about), and metric-shaped words inside docstrings (which
    document series and must not drift). Histogram references may use the
    exposition suffixes of a registered base name.
    """

    id = 'metric-name-catalog'
    description = 'metric name not registered in the instruments catalog'
    catalog_label = 'metric-name'

    def catalog(self, project: Project) -> frozenset[str]:
        return project.metric_catalog()

    @staticmethod
    def _is_registered(word: str, registered: frozenset[str]) -> bool:
        base = _EXPOSITION_SUFFIX_RE.sub('', word)
        return word in registered or base in registered

    @staticmethod
    def _string_constants(source: SourceFile) -> dict[str, str]:
        """``NAME = 'literal'`` bindings anywhere in the module, so a
        metric registered through a named constant
        (``registry.counter(_NAME, ...)``) is still checked — the legacy
        everywhere-scan caught the literal at its definition site; the
        scoped rule must not lose that registration."""
        out: dict[str, str] = {}
        for node in source.nodes():
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target = node.targets[0]
            elif isinstance(node, ast.AnnAssign):  # _NAME: Final = '...'
                target = node.target
            else:
                continue
            if not (
                isinstance(target, ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                continue
            # A name rebound to different strings is ambiguous: drop it.
            if target.id in out and out[target.id] != node.value.value:
                out[target.id] = ''
            else:
                out[target.id] = node.value.value
        return {k: v for k, v in out.items() if v}

    def check(self, source: SourceFile, project: Project):
        assert source.tree is not None
        registered = self.catalog(project)
        if not registered:
            return  # check_project already flagged the broken catalog
        constants = self._string_constants(source)
        # instruments.py registration call sites ARE the catalog, but its
        # docstrings still document series and must not drift (the loop
        # below runs for every file).
        is_catalog_file = source.rel == Project.INSTRUMENTS_REL
        for node in (() if is_catalog_file else source.nodes()):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ('counter', 'gauge', 'histogram')
                and node.args
            ):
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and isinstance(first.value, str):
                name = first.value
            elif isinstance(first, ast.Name) and first.id in constants:
                name = constants[first.id]
            else:
                continue
            if not self._is_registered(name, registered):
                yield self.diag(
                    source,
                    node.lineno,
                    f'metric {name!r} registered at a call site but '
                    'missing from the instruments.py catalog',
                )
        for lineno, text in _docstrings(source):
            for word in _WORD_RE.findall(text):
                if (
                    not _METRIC_NAME_RE.match(word)
                    or word.startswith('distllm_tpu')
                    or word.endswith('_')  # doc glob, e.g. a *-suffix family
                ):
                    continue
                if not self._is_registered(word, registered):
                    yield self.diag(
                        source,
                        lineno,
                        f'docstring references metric {word!r} which is '
                        'not in the instruments.py catalog',
                    )


@register
class FlightKindCatalogRule(_CatalogRule):
    """Every FlightRecorder ``kind`` emitted in the package (a string
    literal — or a conditional between string literals — as the first
    argument of a ``.record(...)`` / ``_record_step(...)`` call) must be
    registered in ``instruments.FLIGHT_KINDS``. A kind minted at a call
    site would silently fragment the flight schema that debug bundles,
    ``/debug/flight``, and ``aggregate.py`` replay."""

    id = 'flight-kind-catalog'
    description = 'flight-record kind missing from instruments.FLIGHT_KINDS'
    catalog_label = 'flight-kind'

    def catalog(self, project: Project) -> frozenset[str]:
        return project.frozenset_catalog('FLIGHT_KINDS')

    def check(self, source: SourceFile, project: Project):
        assert source.tree is not None
        registered = self.catalog(project)
        if not registered:
            return
        for node in source.nodes():
            if not (isinstance(node, ast.Call) and node.args):
                continue
            func = node.func
            name = (
                func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name)
                else None
            )
            if name not in ('record', '_record_step'):
                continue
            first = node.args[0]
            branches = (
                (first.body, first.orelse)
                if isinstance(first, ast.IfExp)
                else (first,)
            )
            for branch in branches:
                if not (
                    isinstance(branch, ast.Constant)
                    and isinstance(branch.value, str)
                ):
                    continue
                if branch.value not in registered:
                    yield self.diag(
                        source,
                        node.lineno,
                        f'flight kind {branch.value!r} is not registered '
                        'in instruments.FLIGHT_KINDS',
                    )


@register
class TraceCategoryCatalogRule(_CatalogRule):
    """Every trace-event category the package emits (a string literal
    passed as a ``cat=...`` keyword or a ``'cat': ...`` dict key) must be
    registered in ``instruments.TRACE_EVENT_CATEGORIES`` — a category
    minted at a call site would fragment the trace schema Perfetto
    queries, the exporter validator, and downstream tooling filter on."""

    id = 'trace-category-catalog'
    description = (
        'trace-event category missing from '
        'instruments.TRACE_EVENT_CATEGORIES'
    )
    catalog_label = 'trace-category'

    def catalog(self, project: Project) -> frozenset[str]:
        return project.frozenset_catalog('TRACE_EVENT_CATEGORIES')

    def check(self, source: SourceFile, project: Project):
        assert source.tree is not None
        registered = self.catalog(project)
        if not registered:
            return
        for node in source.nodes():
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if (
                        kw.arg == 'cat'
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)
                        and kw.value.value not in registered
                    ):
                        yield self.diag(
                            source,
                            node.lineno,
                            f'trace category {kw.value.value!r} is not in '
                            'instruments.TRACE_EVENT_CATEGORIES',
                        )
            elif isinstance(node, ast.Dict):
                for key, value in zip(node.keys, node.values):
                    if (
                        isinstance(key, ast.Constant)
                        and key.value == 'cat'
                        and isinstance(value, ast.Constant)
                        and isinstance(value.value, str)
                        and value.value not in registered
                    ):
                        yield self.diag(
                            source,
                            value.lineno,
                            f'trace category {value.value!r} is not in '
                            'instruments.TRACE_EVENT_CATEGORIES',
                        )


@register
class CompilePhaseCatalogRule(_CatalogRule):
    """Every startup/compile phase the package opens (a string literal as
    the first argument of a ``.phase(...)`` call — ``CompileWatcher.phase``)
    must be registered in ``instruments.COMPILE_PHASES``; a phase minted
    at a call site would fragment the startup schema that debug bundles
    and the Perfetto startup track replay."""

    id = 'compile-phase-catalog'
    description = 'compile-phase kind missing from instruments.COMPILE_PHASES'
    catalog_label = 'compile-phase'

    def catalog(self, project: Project) -> frozenset[str]:
        return project.frozenset_catalog('COMPILE_PHASES')

    def check(self, source: SourceFile, project: Project):
        assert source.tree is not None
        registered = self.catalog(project)
        if not registered:
            return
        for node in source.nodes():
            if not (isinstance(node, ast.Call) and node.args):
                continue
            func = node.func
            if not (
                isinstance(func, ast.Attribute) and func.attr == 'phase'
            ):
                continue
            first = node.args[0]
            if (
                isinstance(first, ast.Constant)
                and isinstance(first.value, str)
                and first.value not in registered
            ):
                yield self.diag(
                    source,
                    node.lineno,
                    f'compile phase {first.value!r} is not registered in '
                    'instruments.COMPILE_PHASES',
                )


@register
class StepSpanCatalogRule(_CatalogRule):
    """Every serving-path step span the package opens (a string literal —
    or a conditional between string literals — as the first argument of a
    ``.mark(...)`` / ``.inside(...)`` / ``._span(...)`` call, ``observability/steps.py``
    ``StepSpan``) must be registered in ``instruments.STEP_SPANS``. The
    span is a ``distllm:<name>`` annotation that trace reductions group
    idle gaps by; a name minted at a call site would be a gap nobody's
    reader knows."""

    id = 'step-span-catalog'
    description = 'step span missing from instruments.STEP_SPANS'
    catalog_label = 'step-span'

    def catalog(self, project: Project) -> frozenset[str]:
        return project.frozenset_catalog('STEP_SPANS')

    def check(self, source: SourceFile, project: Project):
        assert source.tree is not None
        registered = self.catalog(project)
        if not registered:
            return
        for node in source.nodes():
            if not (isinstance(node, ast.Call) and node.args):
                continue
            func = node.func
            if not (
                isinstance(func, ast.Attribute)
                and func.attr in ('mark', 'inside', '_span')
            ):
                continue
            first = node.args[0]
            branches = (
                (first.body, first.orelse)
                if isinstance(first, ast.IfExp)
                else (first,)
            )
            for branch in branches:
                if (
                    isinstance(branch, ast.Constant)
                    and isinstance(branch.value, str)
                    and branch.value not in registered
                ):
                    yield self.diag(
                        source,
                        node.lineno,
                        f'step span {branch.value!r} is not registered in '
                        'instruments.STEP_SPANS',
                    )
