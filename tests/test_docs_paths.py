"""Every file a user-facing document names must exist.

One case per document. A backticked (or fenced) token that ends in a
source extension is a claim that the file is there: a path under one of
the repo's top-level directories must resolve from the root, a bare
``name.py`` must be some file's name in the tree, and ``path:line`` must
not point past the file's end. ``ROADMAP.md``, ``PERF.md`` and
``CHANGES.md`` carry history (they name deleted files on purpose) and
are not checked.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

DOCUMENTS = (
    'README.md',
    'examples/README.md',
    '.claude/skills/verify/SKILL.md',
    *sorted(f'docs/{p.name}' for p in (REPO / 'docs').glob('*.md')),
)

_PREFIXES = (
    'distllm_tpu/', 'scripts/', 'tests/', 'benchmarks/', 'examples/', 'docs/',
)
_CODE = re.compile(r'```.*?```|`[^`]+`', re.DOTALL)
_PATH = re.compile(
    r'(?<![\w./-])([\w.-]+(?:/[\w.-]+)*\.(?:py|md|json|yaml|cpp))'
    r'(?::(\d+))?(?![\w/])'
)
# Bare names are resolved by basename; data files a reader is told to
# write (``out.json``) are not claims about the tree.
_BARE_CHECKED = ('.py', '.md', '.cpp')
_SKIP_DIRS = {'chiprun_out', 'scratch_chip', '__pycache__'}


def _basenames() -> set[str]:
    names: set[str] = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if d not in _SKIP_DIRS and (not d.startswith('.') or d == '.claude')
        ]
        names.update(files)
    return names


def missing_paths(text: str, basenames: set[str]) -> list[str]:
    """The problems of one document's text, as readable strings."""
    problems: set[str] = set()
    for span in _CODE.findall(text):
        for path, line in _PATH.findall(span):
            if path.startswith(_PREFIXES):
                target = REPO / path
                if not target.is_file():
                    problems.add(f'{path}: no such file')
                elif line and int(line) > len(target.read_text().splitlines()):
                    problems.add(f'{path}:{line}: past the end of the file')
            elif (
                '/' not in path  # package- or document-relative: not judged
                and path.endswith(_BARE_CHECKED)
                and path not in basenames
            ):
                problems.add(f'{path}: no file of that name')
    return sorted(problems)


@pytest.fixture(scope='module')
def basenames() -> set[str]:
    return _basenames()


@pytest.mark.parametrize('document', DOCUMENTS)
def test_named_files_exist(document, basenames):
    problems = missing_paths((REPO / document).read_text(), basenames)
    assert not problems, f'{document} names: ' + '; '.join(problems)


def test_checker_sees_a_deleted_file_and_a_line_past_the_end(basenames):
    text = (
        'Run `python no_such_stage.py --stage gen`, read '
        '`scripts/no_such_probe.py` and `tests/conftest.py:100000`; '
        '`tests/conftest.py:1` and `out.json` are fine.\n'
        '```\n$ python scripts/no_such_gate.py older.json\n```\n'
    )
    assert missing_paths(text, basenames) == [
        'no_such_stage.py: no file of that name',
        'scripts/no_such_gate.py: no such file',
        'scripts/no_such_probe.py: no such file',
        'tests/conftest.py:100000: past the end of the file',
    ]
