"""Bench trajectory gate: diff any set of driver bench records.

Reading a trajectory by eyeballing JSON lets an improvement and an
all-zero round coexist with no gate noticing either. This script turns a
pile of records into a gate:

- load any set of record files (the driver-contract JSON: ``{"n", "cmd",
  "rc", "parsed": {...}}``, or a bare metrics object), oldest first;
- extract the numeric metrics from each record's ``parsed`` payload
  (records that died before emitting — ``parsed: null`` — contribute an
  explicitly empty column, not a crash);
- emit a markdown trajectory table (one row per metric, one column per
  round, delta column for the newest round);
- **gate**: compare the newest record against the most recent prior
  record carrying each gated metric; exit nonzero when a throughput /
  MFU / goodput metric fell (or a latency / warmup metric rose) by more
  than ``--threshold`` (default 5%). Metrics present earlier but missing
  from the newest record are reported as *lost* — a warning by default,
  a failure under ``--strict-missing``.

Usage::

    python scripts/benchdiff.py older.json newer.json
    python scripts/benchdiff.py records/*.json --markdown TRAJECTORY.md
    python scripts/benchdiff.py older.json candidate.json --threshold 0.03
    python scripts/benchdiff.py records/*.json --emit-baseline baseline.json

``--emit-baseline`` distills the newest record that carried metrics into
the **baseline envelope** the runtime regression sentinel consumes
(``distllm_tpu/observability/sentinel.py``; arm a server with
``DISTLLM_BASELINE=<path>``). Record parsing and gate directions live in
``distllm_tpu.observability.baseline`` — SHARED with the sentinel, so
the offline gate and the runtime sentinel can never disagree on what a
record says; this script re-exports them for its library consumers.

Runs in the fast test tier over the real r01/r02 records
(``tests/test_benchdiff.py``); dependency-free (no jax import).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

from distllm_tpu.observability.baseline import (  # noqa: E402
    envelope_from_records,
    extract_metrics,
    gate_direction,
    load_record,
)

__all__ = [
    'diff_records',
    'envelope_from_records',
    'extract_metrics',
    'format_markdown',
    'gate_direction',
    'load_record',
    'main',
]


def diff_records(
    records: list[dict], threshold: float
) -> tuple[list[dict], list[str]]:
    """Gate the NEWEST record against the most recent prior value of each
    gated metric. Returns ``(regressions, lost)``:

    - regressions: ``{'key', 'prior', 'prior_name', 'current', 'delta'}``
      for each gated metric that moved in the bad direction by more than
      ``threshold`` (fractional);
    - lost: gated metric keys present in some prior record but absent
      from the newest one.

    Comparison is newest-vs-most-recent-prior (not first-vs-last): the
    gate answers "did the round under review regress?", and older rounds'
    internal history is the table's job, not the gate's.
    """
    if len(records) < 2:
        return [], []
    current = records[-1]
    regressions: list[dict] = []
    lost: list[str] = []
    gated_keys = sorted({
        key
        for record in records
        for key in record['metrics']
        if gate_direction(key) is not None
    })
    for key in gated_keys:
        prior = prior_name = None
        for record in reversed(records[:-1]):
            if key in record['metrics']:
                prior = record['metrics'][key]
                prior_name = record['name']
                break
        if prior is None:
            continue  # brand-new metric: nothing to regress against
        if key not in current['metrics']:
            lost.append(key)
            continue
        value = current['metrics'][key]
        if prior == 0:
            continue  # no meaningful relative delta off a zero baseline
        delta = (value - prior) / abs(prior)
        bad = (
            delta < -threshold
            if gate_direction(key) == 'higher'
            else delta > threshold
        )
        if bad:
            regressions.append({
                'key': key,
                'prior': prior,
                'prior_name': prior_name,
                'current': value,
                'delta': delta,
            })
    return regressions, lost


def _format_value(value: float | None) -> str:
    if value is None:
        return '—'
    if not math.isfinite(value):  # belt-and-braces: extraction drops these
        return str(value)
    if value == int(value) and abs(value) < 1e12:
        return str(int(value))
    return f'{value:.6g}'


def format_markdown(records: list[dict], threshold: float) -> str:
    """The trajectory table plus the gate verdicts, as markdown."""
    keys = sorted({key for r in records for key in r['metrics']})
    names = [r['name'] for r in records]
    lines = [
        '# Bench trajectory',
        '',
        '| metric | ' + ' | '.join(names) + ' | Δ newest | gate |',
        '| --- |' + ' --- |' * (len(names) + 2),
    ]
    regressions, lost = diff_records(records, threshold)
    regressed = {r['key']: r for r in regressions}
    for key in keys:
        values = [r['metrics'].get(key) for r in records]
        prior = next(
            (v for v in reversed(values[:-1]) if v is not None), None
        )
        current = values[-1]
        if current is None:
            delta = 'lost' if prior is not None else '—'
        elif prior in (None, 0):
            delta = 'new'
        else:
            delta = f'{(current - prior) / abs(prior):+.1%}'
        direction = gate_direction(key)
        if direction is None:
            gate = ''
        elif key in regressed:
            gate = '**REGRESSED**'
        elif key in lost:
            gate = 'lost'
        else:
            gate = 'ok'
        lines.append(
            f'| {key} | '
            + ' | '.join(_format_value(v) for v in values)
            + f' | {delta} | {gate} |'
        )
    errors = [(r['name'], r['error']) for r in records if r.get('error')]
    if errors:
        lines.append('')
        for name, error in errors:
            lines.append(f'- `{name}`: {error}')
    lines.append('')
    if regressions:
        lines.append(
            f'**{len(regressions)} regression(s)** beyond '
            f'{threshold:.0%} in `{records[-1]["name"]}`:'
        )
        for reg in regressions:
            lines.append(
                f'- `{reg["key"]}`: {_format_value(reg["prior"])} '
                f'(`{reg["prior_name"]}`) → {_format_value(reg["current"])} '
                f'({reg["delta"]:+.1%})'
            )
    elif lost:
        lines.append(
            f'No regressions among reported metrics; {len(lost)} gated '
            f'metric(s) missing from `{records[-1]["name"]}`: '
            + ', '.join(f'`{k}`' for k in lost)
        )
    else:
        lines.append(f'No regressions beyond {threshold:.0%}.')
    return '\n'.join(lines) + '\n'


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        'records', nargs='+',
        help='record files, oldest first',
    )
    parser.add_argument(
        '--threshold', type=float, default=0.05,
        help='fractional regression threshold (default 0.05 = 5%%)',
    )
    parser.add_argument(
        '--markdown', type=str, default=None,
        help='also write the trajectory table to this path',
    )
    parser.add_argument(
        '--strict-missing', action='store_true',
        help='treat gated metrics missing from the newest record as '
             'regressions (off by default: the r03-r05 tail is known-bad)',
    )
    parser.add_argument(
        '--emit-baseline', type=str, default=None, metavar='PATH',
        help='write the baseline envelope (newest record with metrics) '
             'for the runtime regression sentinel; works with any record '
             'count — zero usable records emits an empty envelope the '
             'sentinel disarms on (counted), never a crash',
    )
    args = parser.parse_args(argv)

    records = [load_record(path) for path in args.records]
    if args.emit_baseline is not None:
        envelope = envelope_from_records(records)
        Path(args.emit_baseline).write_text(
            json.dumps(envelope, indent=2) + '\n'
        )
        print(
            f'baseline envelope -> {args.emit_baseline} '
            f'({len(envelope["metrics"])} metric(s) from '
            f'{envelope["source"] or "no usable record"})'
        )
        if len(records) < 2:
            return 0  # envelope-only invocation: nothing to diff
    if len(records) < 2:
        print('need at least two records to diff', file=sys.stderr)
        return 2
    report = format_markdown(records, args.threshold)
    sys.stdout.write(report)
    if args.markdown:
        Path(args.markdown).write_text(report)
    regressions, lost = diff_records(records, args.threshold)
    if regressions:
        return 1
    if lost and args.strict_missing:
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
