#!/usr/bin/env python
"""Blob gate — fail CI when a summary blob made afresh breaks its contract.

``scripts/ci.sh`` makes one-line JSON blobs from CPU smoke runs
(``report.py --bench-json``, the streamed smoke, fleet_campaign's
summaries) and this gate holds each to a committed tolerance file:
structure, byte counts, accuracy floors. It says nothing about speed: that
is ``benchmark/`` on the chip, and its record is ``PERF_LEDGER.jsonl``.

    python scripts/bench_gate.py fresh.json --gate scripts/ci_bench_gate.json
    python scripts/bench_gate.py fresh.json --baseline earlier.json \
        --min-ratio 0.9

Exit 0 = every gated metric within tolerance; exit 1 = regression (the
offending rows are printed); exit 2 = usage/shape error.

Gate file schema (JSON; the committed CI instance is
``scripts/ci_bench_gate.json``)::

    {"metrics": {
        "fedavg_rounds_per_sec": {"baseline": 1.2, "min_ratio": 0.05},
        "final_test_acc":        {"min_abs": 0.9},
        "rounds":                {"baseline": 2, "exact": true}}}

Per-metric checks (any combination; all must hold):

- ``min_ratio``/``max_ratio`` — fresh vs ``baseline`` ratio bounds
  (throughput floors, byte ceilings);
- ``min_abs``/``max_abs`` — absolute bounds (accuracy floors);
- ``exact``   — fresh == baseline (structural fields like round counts);
- ``required``— missing-from-fresh is a failure (default: skip + warn,
  so one gate file can serve blobs from different modes).

Metric names resolve against the blob's headline (``metric``/``value``
pair) first, then its top-level keys — so ``fedavg_rounds_per_sec``
reads ``value`` while ``final_test_acc`` reads the side field.
"""

from __future__ import annotations

import argparse
import json
import sys


def _load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object, got "
                         f"{type(doc).__name__}")
    return doc


def resolve_metric(blob: dict, name: str):
    """The value ``name`` names inside a BENCH blob: the headline when the
    blob's ``metric`` matches, else the top-level field. None = absent."""
    if blob.get("metric") == name:
        return blob.get("value")
    v = blob.get(name)
    return v if isinstance(v, (int, float, str)) else None


def check_metric(name: str, fresh, spec: dict) -> list[str]:
    """-> list of violation strings (empty = pass)."""
    errs = []
    baseline = spec.get("baseline")
    if spec.get("exact"):
        if fresh != baseline:
            errs.append(f"{name}: {fresh!r} != baseline {baseline!r} (exact)")
        return errs
    try:
        fresh = float(fresh)
    except (TypeError, ValueError):
        return [f"{name}: non-numeric fresh value {fresh!r}"]
    for key, op in (("min_abs", lambda v, t: v >= t),
                    ("max_abs", lambda v, t: v <= t)):
        if key in spec and not op(fresh, float(spec[key])):
            errs.append(f"{name}: {fresh:g} violates {key}={spec[key]:g}")
    for key in ("min_ratio", "max_ratio"):
        if key not in spec:
            continue
        if not isinstance(baseline, (int, float)) or not baseline:
            errs.append(f"{name}: {key} needs a nonzero numeric 'baseline'")
            continue
        ratio = fresh / float(baseline)
        ok = ratio >= float(spec[key]) if key == "min_ratio" \
            else ratio <= float(spec[key])
        if not ok:
            errs.append(f"{name}: {fresh:g} is {ratio:.3f}x baseline "
                        f"{baseline:g} (violates {key}={spec[key]:g})")
    return errs


def run_gate(fresh: dict, gate: dict) -> tuple[list[str], list[str]]:
    """-> (violations, report lines)."""
    metrics = gate.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        raise ValueError("gate file has no 'metrics' table")
    violations, lines = [], []
    for name, spec in sorted(metrics.items()):
        val = resolve_metric(fresh, name)
        if val is None:
            msg = f"{name}: absent from fresh blob"
            if spec.get("required"):
                violations.append(msg + " (required)")
                lines.append(f"FAIL  {msg} (required)")
            else:
                lines.append(f"skip  {msg}")
            continue
        errs = check_metric(name, val, spec)
        if errs:
            violations.extend(errs)
            lines.extend(f"FAIL  {e}" for e in errs)
        else:
            base = spec.get("baseline")
            detail = (f"{val!r} vs baseline {base!r}" if base is not None
                      else f"{val!r}")
            lines.append(f"ok    {name}: {detail}")
    return violations, lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_gate")
    p.add_argument("fresh", help="fresh summary blob (report.py "
                                 "--bench-json output)")
    p.add_argument("--gate", default=None, metavar="PATH",
                   help="committed gate file with per-metric tolerances "
                        "(see module docstring for the schema)")
    p.add_argument("--baseline", default=None, metavar="PATH",
                   help="blob-vs-blob mode: gate the fresh blob's headline "
                        "metric against this blob's at --min-ratio")
    p.add_argument("--min-ratio", type=float, default=0.9,
                   help="blob-vs-blob throughput floor "
                        "(fresh/baseline; default 0.9)")
    args = p.parse_args(argv)
    if bool(args.gate) == bool(args.baseline):
        print("bench_gate: pass exactly one of --gate / --baseline",
              file=sys.stderr)
        return 2

    try:
        fresh = _load(args.fresh)
        if args.gate:
            gate = _load(args.gate)
        else:
            base = _load(args.baseline)
            name = base.get("metric") or fresh.get("metric")
            if name is None or base.get("value") is None:
                raise ValueError(f"{args.baseline}: no metric/value headline "
                                 "to gate against")
            gate = {"metrics": {name: {"baseline": base["value"],
                                       "min_ratio": args.min_ratio,
                                       "required": True}}}
        violations, lines = run_gate(fresh, gate)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_gate: {e}", file=sys.stderr)
        return 2
    print("\n".join(lines))
    if violations:
        print(f"bench_gate: REGRESSION — {len(violations)} violation(s)",
              file=sys.stderr)
        return 1
    print(f"bench_gate: ok ({sum(1 for ln in lines if ln.startswith('ok'))} "
          f"metric(s) within tolerance)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
