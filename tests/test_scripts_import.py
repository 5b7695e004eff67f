"""Every operator script left in ``scripts/`` still starts.

One case per script: run it in this process as ``__main__`` with
``--help``. That executes its module-level imports and its argument
parser, so a script that imports a module nobody ships (as a deleted
probe did from the old benchmark) fails here, not on an operator's
machine. ``--help`` exits 0 before any work is done
(``aot_preflight.py`` parses before it imports jax, so that no test
worker loads libtpu: of it only the parser is checked).
"""

from __future__ import annotations

import runpy
import sys
from pathlib import Path

import pytest

SCRIPTS = sorted((Path(__file__).resolve().parent.parent / 'scripts').glob('*.py'))


@pytest.mark.parametrize('script', SCRIPTS, ids=lambda p: p.name)
def test_script_help_exits_zero(script, monkeypatch, capsys):
    monkeypatch.setattr(sys, 'argv', [str(script), '--help'])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(str(script), run_name='__main__')
    assert done.value.code == 0
    assert 'usage' in capsys.readouterr().out.lower()
