#!/usr/bin/env python
"""Run reporter — render a telemetry event log as a round-by-round table
and (optionally) a BENCH-compatible JSON summary.

    python scripts/report.py runs/mnist/events.jsonl
    python scripts/report.py runs/mnist/events.jsonl --bench-json -   # stdout
    python scripts/report.py runs/mnist/events.jsonl \
        --bench-json summary.json --csv rounds.csv

Input: the events.jsonl a Telemetry run writes (FedAvgAPI(telemetry=...),
distributed_launch --telemetry-dir); rotated segments (events.jsonl.N) are
folded back in automatically. Schema: docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt(v, width: int) -> str:
    if v is None or v == "":
        s = "-"
    elif isinstance(v, float):
        s = f"{v:.4g}"
    else:
        s = str(v)
    return s.rjust(width)


def _staleness_quantile(rec: dict, q: float):
    """Per-round staleness quantile from an async round record's folded
    staleness list; None (column hides) on pre-async logs or sync runs."""
    st = (rec.get("async") or {}).get("staleness")
    if not st:
        return None
    st = sorted(st)
    return st[min(int(q * (len(st) - 1) + 0.5), len(st) - 1)]


def _shed_total(rec: dict):
    shed = (rec.get("async") or {}).get("shed")
    if shed is None:
        return None
    return int(sum(shed.values()))


def render_table(records: list[dict]) -> str:
    """Round-by-round text table; eval rows are folded into their round."""
    evals: dict[int, dict] = {}
    for r in records:
        if r.get("kind") == "eval" and r.get("eval"):
            evals[int(r["round"])] = r["eval"]
    rows = []
    for r in records:
        if r.get("kind") != "round":
            continue
        m = r.get("metrics", {})
        sp = r.get("spans", {})
        ev = r.get("eval") or evals.get(int(r["round"])) or {}
        n = max(float(m.get("count", 0.0)), 1.0)
        rows.append({
            "round": r["round"],
            "clients": len(r.get("clients", [])) or None,
            "round_s": sp.get("round"),
            "pack_s": sp.get("pack") or sp.get("prefetch_pack"),
            "agg_s": sp.get("aggregate"),
            # pipelined rounds (docs/PERFORMANCE.md): host stall waiting on
            # the prefetch thread, H2D issue time, and the async-dispatch
            # depth at push — columns hide on non-pipelined logs
            "stall_s": sp.get("prefetch_stall"),
            "h2d_s": sp.get("h2d"),
            "depth": (r.get("pipeline") or {}).get("depth"),
            # sharded-server-state runs (docs/PERFORMANCE.md §Partitioned
            # server state): aggregation mode + per-device server-plane
            # bytes — columns hide on logs that predate the field
            "srv": (r.get("agg") or {}).get("mode"),
            "srv_dev_B": (r.get("agg") or {}).get(
                "server_state_bytes_per_device"),
            # fused aggregation + mixed precision (docs/PERFORMANCE.md
            # §Fused aggregation / §Mixed precision): server flush latency
            # (fused or stacked) and the client-compute precision policy —
            # both hide gracefully on logs that predate the fields
            "flush_s": (r.get("agg") or {}).get("flush_s"),
            "prec": (r.get("agg") or {}).get("prec"),
            # buffered-async runs (docs/ROBUSTNESS.md §Asynchronous
            # buffered rounds): buffer size folded, staleness quantiles of
            # the folded updates, cumulative shed count, buffer fill time
            # — columns hide on pre-async logs
            # size-bucketed cohort packing (docs/PERFORMANCE.md §Streaming
            # & cohort bucketing): dispatched bucket depth vs the cohort's
            # natural need, and the padded-slot fraction — columns hide on
            # logs that predate the pack block
            "bkt_B": (r.get("pack") or {}).get("bucket_B"),
            "pad_frac": (r.get("pack") or {}).get("pad_frac"),
            # hierarchical 2-tier runs (docs/ROBUSTNESS.md §Hierarchical
            # tiers): the root's realized fan-in (== edge count); with
            # cross-tier robust gating (§Cross-tier robust gating), the
            # round's total rejected slots over the per-edge counts and
            # the verdict fan-out -> last-partial round-trip latency —
            # both hide on pre-cross-tier logs
            "fan_in": (r.get("hier") or {}).get("fan_in"),
            "rej": (sum((r.get("hier") or {}).get("rejected"))
                    if (r.get("hier") or {}).get("rejected") is not None
                    else None),
            "vrtt_s": (r.get("hier") or {}).get("verdict_rtt_s"),
            # masked secure aggregation + privacy ledger
            # (docs/ROBUSTNESS.md §Secure aggregation / §Privacy ledger):
            # how the round decoded (full | recovered | shed attempts
            # surface via the ledger), and the DP accountant's cumulative
            # ε@δ — both hide on logs that predate the blocks
            "secagg": (r.get("secagg") or {}).get("outcome"),
            "eps": (r.get("privacy") or {}).get("eps"),
            # per-client privacy ledger (docs/ROBUSTNESS.md §Hierarchical
            # secure aggregation): the worst single client's ε@δ — hides
            # on logs that predate the per-client ledger
            "eps_cli": (r.get("privacy") or {}).get("eps_client_max"),
            # server crash recovery (docs/ROBUSTNESS.md §Server crash
            # recovery): cumulative supervised restarts behind this round
            # — the column hides on runs (and pre-WAL logs) that never
            # crashed
            "restarts": (r.get("server") or {}).get("restarts"),
            "buf_k": (r.get("async") or {}).get("k"),
            "stale_p50": _staleness_quantile(r, 0.5),
            "stale_max": _staleness_quantile(r, 1.0),
            "shed": _shed_total(r),
            "fill_s": (r.get("async") or {}).get("buffer_fill_s"),
            "loss": (m["loss_sum"] / n) if "loss_sum" in m else None,
            "upd_norm": m.get("update_norm"),
            "drift": m.get("client_drift_mean"),
            "test_acc": ev.get("test_acc"),
            "tx_msgs": r.get("comm", {}).get("messages_sent"),
            "tx_bytes": r.get("comm", {}).get("bytes_sent"),
            # per-direction wire accounting (comm_bytes_total{direction},
            # docs/PERFORMANCE.md §Wire efficiency): uplink is the byte
            # budget the delta/quantized tiers optimize — columns hide on
            # pre-PR-9 logs that predate the split
            "tx_up_B": r.get("comm", {}).get("bytes_uplink"),
            "tx_down_B": r.get("comm", {}).get("bytes_downlink"),
            # memory telemetry (obs/memwatch.py, docs/OBSERVABILITY.md
            # §Memory telemetry): host RSS + summed device bytes at emit —
            # columns hide on logs that predate the mem block
            "rss_B": (r.get("mem") or {}).get("host_rss_bytes"),
            "dev_B": (r.get("mem") or {}).get("device_bytes_in_use"),
            # round economics (obs/goodput.py, docs/PERFORMANCE.md §Round
            # economics): duty fractions of the headline buckets, useful
            # device throughput, and MFU when the device kind resolved —
            # columns hide on logs that predate the goodput block
            "duty_cmp": ((r.get("goodput") or {}).get("duty")
                         or {}).get("compute"),
            "duty_stall": ((r.get("goodput") or {}).get("duty")
                           or {}).get("prefetch_stall"),
            "gflops": ((r.get("goodput") or {}).get("flops_per_s") / 1e9
                       if (r.get("goodput") or {}).get("flops_per_s")
                       is not None else None),
            "mfu": (r.get("goodput") or {}).get("mfu"),
        })
    if not rows:
        return "(no round records)"
    cols = [c for c in rows[0] if any(row[c] is not None for row in rows)]
    widths = {c: max(len(c), *(len(_fmt(row[c], 0).strip()) for row in rows))
              for c in cols}
    lines = ["  ".join(c.rjust(widths[c]) for c in cols)]
    lines.append("  ".join("-" * widths[c] for c in cols))
    for row in rows:
        lines.append("  ".join(_fmt(row[c], widths[c]) for c in cols))
    return "\n".join(lines)


def render_compiles(records: list[dict]) -> str:
    """The compile observatory (obs/perf_instrument.py per-variant
    attribution + the warmup report's per-variant wall): one line per
    compiled variant with AOT wall, backend compile seconds, and
    hit/miss counts. Logs that predate the observatory degrade to a
    notice — same contract as the goodput columns."""
    recs = [r for r in records if r.get("kind") == "compiles"]
    if not recs:
        return ("(no compile records — run predates the compile "
                "observatory, or warmup was skipped)")
    lines = []
    for rec in recs:
        lines.append(f"compiles: total={rec.get('seconds', 0):.2f}s  "
                     f"fresh={rec.get('fresh')}  "
                     f"cache_hits={rec.get('cache_hits')}  "
                     f"cache_misses={rec.get('cache_misses')}  "
                     f"instrumented={rec.get('instrumented')}")
        attr = rec.get("attribution") or {}
        names = sorted(set(rec.get("variants") or {}) | set(attr))
        if not names:
            continue
        rows = []
        for name in names:
            a = attr.get(name) or {}
            v = (rec.get("variants") or {}).get(name)
            aot = v.get("seconds") if isinstance(v, dict) else v
            rows.append((name,
                         _fmt(aot, 0),
                         _fmt(a.get("seconds"), 0),
                         _fmt(a.get("compiles"), 0),
                         _fmt(a.get("cache_hits"), 0),
                         _fmt(a.get("cache_misses"), 0)))
        cols = ("variant", "aot_s", "backend_s", "compiles", "hits",
                "misses")
        widths = [max(len(cols[i]), *(len(r[i].strip()) for r in rows))
                  for i in range(len(cols))]
        lines.append("  " + "  ".join(c.rjust(w)
                                      for c, w in zip(cols, widths)))
        lines.extend("  " + "  ".join(v.strip().rjust(w)
                                      for v, w in zip(r, widths))
                     for r in rows)
    return "\n".join(lines)


def render_alerts(records: list[dict]) -> str:
    """The run's health-alert ledger (obs/health.py): one line per
    fired/resolved transition with the measured value vs the rule's
    threshold. Logs that predate the health layer degrade to a notice —
    same contract as the async/codec columns."""
    alerts = [r for r in records if r.get("kind") == "alert"]
    if not alerts:
        return ("(no alert records — clean run, or the log predates the "
                "health monitor)")
    lines = ["alerts:"]
    for a in alerts:
        val = a.get("value")
        val_s = f"{val:.4g}" if isinstance(val, (int, float)) else "nan"
        lines.append(
            f"  {a.get('state', '?'):>8}  {a.get('rule', '?'):<14}"
            f"severity={a.get('severity', '?'):<9}"
            f"round={a.get('round') if a.get('round') is not None else '-':<6}"
            f"value={val_s} threshold={a.get('threshold')}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("fedml_tpu run reporter")
    p.add_argument("events", help="path to a run's events.jsonl")
    p.add_argument("--bench-json", default=None, metavar="PATH",
                   help="also write the BENCH-compatible summary blob "
                        "('-' = stdout as the last line)")
    p.add_argument("--csv", default=None, metavar="PATH",
                   help="also write the round records as CSV")
    p.add_argument("--alerts", action="store_true",
                   help="render the run's health-alert ledger (rule, "
                        "severity, fired/resolved round, value vs "
                        "threshold — obs/health.py); logs that predate "
                        "the health monitor degrade to a notice")
    p.add_argument("--compiles", action="store_true",
                   help="render the compile observatory: per-variant AOT "
                        "wall, backend compile seconds, and cache hit/"
                        "miss attribution from warmup's 'compiles' event "
                        "record (obs/perf_instrument.py); logs that "
                        "predate the observatory degrade to a notice")
    p.add_argument("--critical-path", action="store_true",
                   help="render the per-round critical-path/straggler "
                        "attribution (straggler rank, phase breakdown, "
                        "per-rank slack, chaos-injected delay) from a "
                        "tracing-enabled run's round records; logs that "
                        "predate tracing degrade to a notice")
    p.add_argument("--post-mortem", action="store_true",
                   help="stitch one crash timeline from the run's WAL, the "
                        "per-rank flight-recorder dumps, and the event "
                        "log's alert/header records (obs/flightrec.py, "
                        "docs/OBSERVABILITY.md §Flight recorder & post-"
                        "mortem); restart records are flagged and the "
                        "pre-crash window starred. Logs that predate the "
                        "fleet plane degrade to a notice")
    p.add_argument("--wal-dir", default=None, metavar="DIR",
                   help="--post-mortem: the server's WAL directory "
                        "(default: <events dir>/wal, the launcher's "
                        "--ckpt_dir layout)")
    p.add_argument("--flightrec-dir", default=None, metavar="DIR",
                   help="--post-mortem: the per-rank flight-dump directory "
                        "(default: <events dir>/flightrec)")
    args = p.parse_args(argv)

    from fedml_tpu.obs.events import read_jsonl
    from fedml_tpu.obs.export import bench_blob, write_csv
    from fedml_tpu.obs.trace_export import render_critical_path

    records = read_jsonl(args.events)
    if not records:
        print(f"report: no records in {args.events}", file=sys.stderr)
        return 1

    headers = [r for r in records if r.get("kind") == "run"]
    if headers:
        h = headers[0]
        print(f"run: {h.get('run')}  engine: {h.get('engine', '?')}")
    print(render_table(records))
    if args.compiles:
        print()
        print(render_compiles(records))
    if args.alerts:
        print()
        print(render_alerts(records))
    if args.critical_path:
        print()
        print(render_critical_path(records))
    if args.post_mortem:
        from fedml_tpu.obs.flightrec import render_post_mortem

        base = os.path.dirname(os.path.abspath(args.events))
        wal_dir = args.wal_dir or os.path.join(base, "wal")
        flight_dir = args.flightrec_dir or os.path.join(base, "flightrec")
        print()
        print(render_post_mortem(wal_dir=wal_dir, flight_dir=flight_dir,
                                 events=records))

    if args.csv:
        cols = write_csv(records, args.csv)
        print(f"report: wrote {args.csv} ({len(cols)} columns)",
              file=sys.stderr)
    if args.bench_json:
        blob = bench_blob(records)
        if args.bench_json == "-":
            print(json.dumps(blob))
        else:
            with open(args.bench_json, "w") as f:
                json.dump(blob, f, indent=2)
            print(f"report: wrote {args.bench_json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
