"""distlint core: single-parse static analysis with structured suppression.

The framework behind ``scripts/distlint.py`` and the tier-1 lint bridge
(``tests/test_lint.py``). Design constraints, in order:

- **dependency-free** — stdlib ``ast`` + ``tokenize`` only, importable on
  any backend (the same bar as the observability stack);
- **one parse per file** — every rule runs over a shared
  :class:`SourceFile` (AST + comment map built once), replacing the
  legacy ``test_lint.py`` pattern of re-walking the tree per rule;
- **suppression is structured and audited** — the only escape hatch is
  an inline ``# distlint: disable=<rule-id> -- <justification>`` comment;
  a suppression without a justification, naming an unknown rule, or
  matching no finding is itself a finding (the framework's meta rules),
  so the allowlist can never silently rot;
- **comments are read from the token stream**, never from raw line
  regexes — a suppression spelled inside a string literal (e.g. a test
  fixture snippet) is data, not a directive.

Rules subclass :class:`Rule` and register with :func:`register`; the
driver is :func:`analyze`. Cross-file context (the instruments.py
catalogs) lives on :class:`Project` and is computed lazily, once.
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass
from pathlib import Path

SEVERITIES = ('error', 'warning')

# Inline directive grammar. Only real COMMENT tokens are consulted, so
# these spellings inside string literals (fixtures, docs) are inert.
_DISABLE_RE = re.compile(
    r'distlint:\s*disable=([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)'
    r'(?:\s+--\s*(.*\S))?\s*$'
)
_MARKER_RE = re.compile(r'distlint:\s*(hot-path|traced)\b')
_GUARDED_RE = re.compile(r'guarded by self\.([A-Za-z_][A-Za-z0-9_]*)')

# Meta rule ids the framework itself emits (not in the registry; they
# cannot be suppressed — the audit trail must not be able to hide itself).
SYNTAX_ERROR = 'syntax-error'
SUPPRESSION_UNJUSTIFIED = 'suppression-unjustified'
SUPPRESSION_UNUSED = 'suppression-unused'
SUPPRESSION_UNKNOWN_RULE = 'suppression-unknown-rule'
META_RULE_IDS = (
    SYNTAX_ERROR,
    SUPPRESSION_UNJUSTIFIED,
    SUPPRESSION_UNUSED,
    SUPPRESSION_UNKNOWN_RULE,
)


@dataclass(frozen=True)
class Diagnostic:
    """One finding: ``rule_id`` at ``path:line`` with a message."""

    rule_id: str
    path: str  # repo-relative posix path
    line: int
    message: str
    severity: str = 'error'

    @property
    def location(self) -> str:
        return f'{self.path}:{self.line}'

    def format(self) -> str:
        return (
            f'{self.location}: {self.severity}: '
            f'[{self.rule_id}] {self.message}'
        )

    def to_dict(self) -> dict[str, object]:
        return {
            'rule_id': self.rule_id,
            'path': self.path,
            'line': self.line,
            'severity': self.severity,
            'message': self.message,
        }


@dataclass
class Suppression:
    """One ``# distlint: disable=...`` directive.

    ``line`` is where the comment sits; ``target_line`` is the line whose
    findings it suppresses — the same line for a trailing comment, the
    next line for a standalone comment line (so long statements can carry
    the directive above themselves).
    """

    line: int
    target_line: int
    rule_ids: tuple[str, ...]
    justification: str
    hits: int = 0

    def matches(self, diag: Diagnostic) -> bool:
        return (
            diag.line == self.target_line and diag.rule_id in self.rule_ids
        )


class SourceFile:
    """One parsed source file: text, AST, comment map, directives.

    Built once per file per run; every rule reads from here. ``tree`` is
    ``None`` when the file does not parse (the driver emits a
    ``syntax-error`` diagnostic and skips rule dispatch for the file).
    """

    def __init__(self, rel: str, text: str, path: Path | None = None):
        self.rel = rel
        self.path = path
        self.text = text
        self.lines = text.splitlines()
        self.tree: ast.Module | None = None
        self.parse_error: SyntaxError | None = None
        try:
            self.tree = ast.parse(text, filename=rel)
        except SyntaxError as exc:
            self.parse_error = exc
        # line -> comment text (without the leading '#'), from the token
        # stream so string-literal look-alikes never register.
        self.comments: dict[int, str] = {}
        # line -> True when the comment is the only thing on its line.
        self._standalone: dict[int, bool] = {}
        try:
            tokens = tokenize.generate_tokens(io.StringIO(text).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                line_no, col = tok.start
                self.comments[line_no] = tok.string.lstrip('#').strip()
                self._standalone[line_no] = not tok.line[:col].strip()
        except (tokenize.TokenError, IndentationError, SyntaxError):
            pass  # unparseable files already carry a syntax-error finding
        # Shared walk caches: rules iterate these instead of re-walking
        # the tree (the single-parse goal extends to single-walk).
        self._nodes: list[ast.AST] | None = None
        self._functions: list[tuple[str, ast.AST]] | None = None
        self.suppressions: list[Suppression] = []
        for line_no, comment in sorted(self.comments.items()):
            match = _DISABLE_RE.search(comment)
            if match is None:
                continue
            ids = tuple(
                part.strip() for part in match.group(1).split(',')
                if part.strip()
            )
            target = (
                line_no + 1 if self._standalone.get(line_no) else line_no
            )
            self.suppressions.append(
                Suppression(
                    line=line_no,
                    target_line=target,
                    rule_ids=ids,
                    justification=(match.group(2) or '').strip(),
                )
            )

    @classmethod
    def from_path(cls, path: Path, root: Path) -> 'SourceFile':
        try:
            rel = path.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:  # outside the root: keep the absolute spelling
            rel = path.resolve().as_posix()
        return cls(rel, path.read_text(), path=path)

    @classmethod
    def from_text(
        cls, text: str, rel: str = 'distllm_tpu/_fixture.py'
    ) -> 'SourceFile':
        """Build a virtual file (tests / fixtures). ``rel`` controls which
        path-scoped rules consider it theirs."""
        return cls(rel, text)

    # ---------------------------------------------------------- markers
    def markers(self, kind: str) -> set[int]:
        """Lines carrying ``# distlint: <kind>`` (``hot-path``/``traced``)."""
        out = set()
        for line_no, comment in self.comments.items():
            match = _MARKER_RE.search(comment)
            if match and match.group(1) == kind:
                out.add(line_no)
        return out

    def guarded_annotations(self) -> dict[int, str]:
        """Lines carrying ``# guarded by self.<lock>`` -> lock attr name."""
        out: dict[int, str] = {}
        for line_no, comment in self.comments.items():
            match = _GUARDED_RE.search(comment)
            if match:
                out[line_no] = match.group(1)
        return out

    # ---------------------------------------------------------- helpers
    def nodes(self) -> list[ast.AST]:
        """Every node of the tree, walked once and cached — rules iterate
        this instead of re-running ``ast.walk`` per rule."""
        if self._nodes is None:
            assert self.tree is not None
            self._nodes = list(ast.walk(self.tree))
        return self._nodes

    def functions(self):
        """``(qualname, node)`` for every function/method, with
        ``Class.method`` / ``outer.<locals>.inner`` dotted qualnames
        (computed once, cached)."""
        if self._functions is not None:
            return self._functions

        out: list[tuple[str, ast.AST]] = []

        def visit(node, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    qual = prefix + child.name
                    out.append((qual, child))
                    visit(child, qual + '.<locals>.')
                elif isinstance(child, ast.ClassDef):
                    visit(child, prefix + child.name + '.')
                else:
                    visit(child, prefix)

        assert self.tree is not None
        visit(self.tree, '')
        self._functions = out
        return out


class Project:
    """The analyzed file set plus lazily-computed cross-file context."""

    INSTRUMENTS_REL = 'distllm_tpu/observability/instruments.py'

    def __init__(self, root: Path, files: list[SourceFile]):
        self.root = Path(root)
        self.files = files
        self._by_rel = {f.rel: f for f in files}
        self._catalog_cache: dict[str, frozenset[str]] = {}

    def file(self, rel: str) -> SourceFile | None:
        return self._by_rel.get(rel)

    # ------------------------------------------------- catalog extraction
    def _instruments_tree(self) -> ast.Module | None:
        source = self.file(self.INSTRUMENTS_REL)
        if source is not None and source.tree is not None:
            return source.tree
        # Running on a path subset must not weaken catalog rules: fall
        # back to reading the catalog straight from the repo.
        path = self.root / self.INSTRUMENTS_REL
        try:
            return ast.parse(path.read_text(), filename=str(path))
        except (OSError, SyntaxError):
            return None

    def metric_catalog(self) -> frozenset[str]:
        """Metric names registered in instruments.py: the first string
        argument of every ``*.counter/gauge/histogram(...)`` call."""
        cached = self._catalog_cache.get('metrics')
        if cached is not None:
            return cached
        names: set[str] = set()
        tree = self._instruments_tree()
        if tree is not None:
            for node in ast.walk(tree):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ('counter', 'gauge', 'histogram')
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    names.add(node.args[0].value)
        result = frozenset(names)
        self._catalog_cache['metrics'] = result
        return result

    def frozenset_catalog(self, name: str) -> frozenset[str]:
        """String members of a ``NAME = frozenset({...})`` assignment in
        instruments.py (flight kinds, trace categories, compile phases)."""
        cached = self._catalog_cache.get(name)
        if cached is not None:
            return cached
        members: set[str] = set()
        tree = self._instruments_tree()
        if tree is not None:
            for node in ast.walk(tree):
                if not isinstance(node, ast.Assign):
                    continue
                for tgt in node.targets:
                    if not (isinstance(tgt, ast.Name) and tgt.id == name):
                        continue
                    call = node.value  # frozenset({...})
                    if isinstance(call, ast.Call) and call.args:
                        members |= {
                            el.value
                            for el in getattr(call.args[0], 'elts', [])
                            if isinstance(el, ast.Constant)
                            and isinstance(el.value, str)
                        }
        result = frozenset(members)
        self._catalog_cache[name] = result
        return result


class Rule:
    """One invariant. Subclass, set the class attributes, implement
    :meth:`check`, and decorate with :func:`register`.

    ``check(source, project)`` yields :class:`Diagnostic` for one file;
    ``check_project(project)`` (optional) runs once per analysis for
    cross-file invariants (e.g. "the catalog parsed non-empty").
    """

    id: str = ''
    description: str = ''
    severity: str = 'error'

    def applies(self, source: SourceFile) -> bool:
        """Path scope; the default is every analyzed file."""
        return True

    def check(self, source: SourceFile, project: Project):
        raise NotImplementedError

    def check_project(self, project: Project):
        return ()

    # Shared scope helpers -------------------------------------------------
    @staticmethod
    def in_package(source: SourceFile) -> bool:
        return source.rel.startswith('distllm_tpu/')

    def diag(self, source: SourceFile, line: int, message: str) -> Diagnostic:
        return Diagnostic(
            rule_id=self.id,
            path=source.rel,
            line=line,
            message=message,
            severity=self.severity,
        )


RULES: dict[str, Rule] = {}


def register(cls: type[Rule]) -> type[Rule]:
    """Class decorator: instantiate and add to the global registry."""
    rule = cls()
    if not rule.id:
        raise ValueError(f'{cls.__name__} has no id')
    if rule.id in RULES or rule.id in META_RULE_IDS:
        raise ValueError(f'duplicate rule id {rule.id!r}')
    if rule.severity not in SEVERITIES:
        raise ValueError(f'{rule.id}: bad severity {rule.severity!r}')
    RULES[rule.id] = rule
    return cls


def iter_rules(ids=None) -> list[Rule]:
    """Registered rules, optionally restricted to ``ids`` (order stable)."""
    if ids is None:
        return [RULES[key] for key in sorted(RULES)]
    unknown = sorted(set(ids) - set(RULES))
    if unknown:
        raise KeyError(f'unknown rule ids: {", ".join(unknown)}')
    return [RULES[key] for key in sorted(set(ids))]


# --------------------------------------------------------------- discovery
def default_source_paths(root: Path) -> list[Path]:
    """The repo's lint surface (mirrors the legacy test_lint SOURCES)."""
    root = Path(root)
    paths = (
        list((root / 'distllm_tpu').rglob('*.py'))
        + list((root / 'scripts').glob('*.py'))
        + list((root / 'tests').glob('*.py'))
    )
    graft_entry = root / '__graft_entry__.py'
    if graft_entry.exists():
        paths.append(graft_entry)
    return sorted(p for p in paths if '__pycache__' not in p.parts)


def load_project(root: Path, paths=None) -> Project:
    root = Path(root)
    if paths is None:
        paths = default_source_paths(root)
    files = [SourceFile.from_path(Path(p), root) for p in paths]
    return Project(root, files)


# ------------------------------------------------------------------ driver
def analyze(
    project: Project,
    rules: list[Rule] | None = None,
    *,
    audit_suppressions: bool = True,
) -> list[Diagnostic]:
    """Run ``rules`` (default: the full registry) over ``project``.

    Each file is parsed exactly once (at :class:`SourceFile` build time);
    rules share that tree. Suppressions are applied after all rules ran,
    then audited: unjustified, unknown-rule, and unused suppressions are
    appended as meta diagnostics. ``audit_suppressions=False`` skips the
    *unused* audit (for single-rule invocations where most directives
    legitimately match nothing).
    """
    if rules is None:
        rules = iter_rules()
    active_ids = {rule.id for rule in rules}
    raw: list[Diagnostic] = []
    for rule in rules:
        raw.extend(rule.check_project(project))
    for source in project.files:
        if source.tree is None:
            error = source.parse_error
            raw.append(
                Diagnostic(
                    rule_id=SYNTAX_ERROR,
                    path=source.rel,
                    line=getattr(error, 'lineno', 1) or 1,
                    message=f'file does not parse: {error}',
                )
            )
            continue
        for rule in rules:
            if rule.applies(source):
                raw.extend(rule.check(source, project))

    kept: list[Diagnostic] = []
    for diag in raw:
        source = project.file(diag.path)
        suppressed = False
        if source is not None and diag.rule_id not in META_RULE_IDS:
            for supp in source.suppressions:
                if supp.matches(diag):
                    supp.hits += 1
                    suppressed = True
        if not suppressed:
            kept.append(diag)

    known_ids = set(RULES) | set(META_RULE_IDS)
    for source in project.files:
        for supp in source.suppressions:
            if not supp.justification:
                kept.append(
                    Diagnostic(
                        rule_id=SUPPRESSION_UNJUSTIFIED,
                        path=source.rel,
                        line=supp.line,
                        message=(
                            'suppression without a justification — write '
                            '"# distlint: disable=<rule-id> -- <why>"'
                        ),
                    )
                )
            for rule_id in supp.rule_ids:
                if rule_id in META_RULE_IDS:
                    # Meta rules are unsuppressible by design; the dead
                    # directive would otherwise accumulate silently (it
                    # never matches and meta ids never enter the unused
                    # audit), misleading readers into thinking it works.
                    kept.append(
                        Diagnostic(
                            rule_id=SUPPRESSION_UNKNOWN_RULE,
                            path=source.rel,
                            line=supp.line,
                            message=(
                                f'suppression names meta rule {rule_id!r},'
                                ' which is not suppressible'
                            ),
                        )
                    )
                elif rule_id not in known_ids:
                    kept.append(
                        Diagnostic(
                            rule_id=SUPPRESSION_UNKNOWN_RULE,
                            path=source.rel,
                            line=supp.line,
                            message=(
                                f'suppression names unknown rule '
                                f'{rule_id!r}'
                            ),
                        )
                    )
            if (
                audit_suppressions
                and supp.hits == 0
                and supp.justification
                and all(rule_id in active_ids for rule_id in supp.rule_ids)
            ):
                kept.append(
                    Diagnostic(
                        rule_id=SUPPRESSION_UNUSED,
                        path=source.rel,
                        line=supp.line,
                        message=(
                            'suppression matched no finding '
                            f'({", ".join(supp.rule_ids)}) — the code is '
                            'clean; delete the directive'
                        ),
                    )
                )
    kept.sort(key=lambda d: (d.path, d.line, d.rule_id, d.message))
    return kept
