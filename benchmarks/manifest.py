"""Where the benchmark's data files are, found by the names in BENCHMARK.json.

A cell, a configuration and a per-layer metric are files of their own:
``workloads/<cell>.json``, ``configs/<config>.json``, ``metrics/<metric>.json``
under one of the directories the manifest lists in ``paths``. A later PR adds
a cell by adding such files and manifest entries; nothing here names a cell.
"""

from __future__ import annotations

import json
from pathlib import Path

PACKAGE_DIR = Path(__file__).resolve().parent
REPO_ROOT = PACKAGE_DIR.parent


class Manifest:
    def __init__(self, path: Path) -> None:
        self.path = Path(path).resolve()
        self.data = json.loads(self.path.read_text())
        base = self.path.parent
        # The manifest's own directories first, then this package, so that a
        # rehearsal manifest can bring tiny cells and still use the readers'
        # metric files.
        self.dirs = [base / p for p in self.data['paths']]
        if PACKAGE_DIR not in self.dirs:
            self.dirs.append(PACKAGE_DIR)

    def find(self, kind: str, name: str) -> Path:
        for directory in self.dirs:
            candidate = directory / kind / f'{name}.json'
            if candidate.is_file():
                return candidate
        raise FileNotFoundError(
            f'no {kind}/{name}.json under {[str(d) for d in self.dirs]}'
        )

    def load(self, kind: str, name: str) -> dict:
        return json.loads(self.find(kind, name).read_text())

    def cell(self, name: str) -> dict:
        for entry in self.data['workloads']:
            if entry['name'] == name:
                return entry
        raise KeyError(f'{name!r} is not a cell of {self.path}')

    def metrics_of(self, group: str, cell: str) -> list[dict]:
        """Entries of ``end_to_end`` or ``per_layer`` that ``cell`` reports:
        those that list it under ``workloads``, and those with no such key."""
        return [
            m for m in self.data[group]
            if 'workloads' not in m or cell in m['workloads']
        ]
