"""``chip_smoke.py`` must not pass anywhere but on the chip.

The script's phases run on a TPU only (a tiny-size CPU rehearsal is for a
builder's hands, not for this tier); what tier-1 pins is the no-fallback
rule: with JAX held to the CPU the script exits non-zero, says "no TPU",
and prints no result line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS='cpu',
        # Nothing may land in the checkout from a test.
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'jax_cache'),
    )
    env.pop('XLA_FLAGS', None)
    proc = subprocess.run(
        [sys.executable, str(REPO / 'chip_smoke.py')],
        capture_output=True, text=True, timeout=300, cwd=tmp_path, env=env,
    )
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert 'no TPU' in proc.stdout + proc.stderr
    for line in proc.stdout.splitlines():
        if line.startswith('{'):
            assert 'ok' not in json.loads(line), line
    assert not (REPO / '.chip_smoke_work').exists()
