"""The sentinel's baseline envelope: schema, maker and reader.

The runtime regression sentinel (``observability/sentinel.py``) compares
*live* history windows against a **baseline envelope**: one value and one
direction for each metric it knows how to read live. This module owns the
envelope's schema, :func:`build_envelope` (a flat dict of measured numbers
in, an envelope out) and :func:`load_envelope` (a file in, a validated
envelope or ``None`` out). Nothing in the repo writes an envelope file
today (ROADMAP D7): an operator who wants the sentinel armed serialises
``build_envelope(...)`` of numbers they trust to ``DISTLLM_BASELINE``.

Dependency-free (no jax, no registry import): the sentinel imports it
inside a serving process.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

ENVELOPE_SCHEMA = 'distllm-baseline-envelope/v1'

# Direction of "better" for an envelope metric, by substring of its name.
_LOWER_BETTER_TOKENS = ('ttft', 'tpot')


def gate_direction(key: str) -> str:
    """``'lower'`` for the latency metrics, ``'higher'`` for every other
    envelope metric (throughput and the measured roofline shares)."""
    k = key.lower()
    if any(token in k for token in _LOWER_BETTER_TOKENS):
        return 'lower'
    return 'higher'


# ------------------------------------------------- the baseline envelope
# Sentinel metric → keys of the measured-numbers dict that can supply its
# baseline, best first. The names mirror instruments.SENTINEL_METRIC_LABELS
# (single owner of the counter label set); this table owns only the key
# mapping. The gen_load / gen_history keys are loadgen-measured serving
# numbers (the closest analog of live traffic).
ENVELOPE_SOURCE_KEYS: dict[str, tuple[str, ...]] = {
    'tok_s': ('gen_load_tok_s', 'gen_history_tok_s', 'gen_value'),
    'ttft_p95_s': ('gen_load_ttft_p95', 'gen_history_ttft_p95'),
    'tpot_p95_s': ('gen_load_tpot_p95', 'gen_history_tpot_p95'),
    'mfu_measured': ('gen_kernel_xla_mfu_measured', 'gen_mfu'),
    'bw_util_measured': ('gen_kernel_xla_bw_util_measured',),
}


def build_envelope(metrics: dict[str, float], *, source: str = '') -> dict:
    """Distill a flat dict of measured numbers into the baseline envelope the
    runtime sentinel consumes. Metrics with no source key present are
    simply absent (the sentinel skips them); an all-absent envelope is
    valid and disarms the sentinel (counted), never raises."""
    envelope_metrics: dict[str, dict] = {}
    for name, candidates in sorted(ENVELOPE_SOURCE_KEYS.items()):
        for key in candidates:
            if key in metrics:
                envelope_metrics[name] = {
                    'value': float(metrics[key]),
                    'direction': gate_direction(name),
                    'from_key': key,
                }
                break
    return {
        'schema': ENVELOPE_SCHEMA,
        'source': source,
        'metrics': envelope_metrics,
    }


def load_envelope(path: str | Path) -> dict | None:
    """Read an envelope file; ``None`` on missing/unreadable/wrong-schema
    (the sentinel turns that into a counted disarm, never a raise)."""
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError, TypeError):
        return None
    if not isinstance(doc, dict) or doc.get('schema') != ENVELOPE_SCHEMA:
        return None
    metrics = doc.get('metrics')
    if not isinstance(metrics, dict):
        return None
    clean: dict[str, dict] = {}
    for name, entry in metrics.items():
        if not isinstance(entry, dict):
            continue
        value = entry.get('value')
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            continue
        if not math.isfinite(value):
            continue
        clean[str(name)] = {
            'value': float(value),
            'direction': entry.get('direction') or gate_direction(name),
            'from_key': entry.get('from_key', ''),
        }
    return {
        'schema': ENVELOPE_SCHEMA,
        'source': str(doc.get('source', '')),
        'metrics': clean,
    }
