"""By hand, on the chip: where a cell's device time goes by the round
program's own scopes, and its idle gaps by the program's own spans.

    python3 benchmark/tests/chip_scopes.py --workload <name> --seed <n>

Prepares the cell as ``run.py`` does (``run.prepare``, ``run.first_units``),
drives one untraced window and one traced window of ``run.TRACE_SECONDS``
through ``engine.drive_window``, keeps the trace under
``chiprun_out/scopes_<workload>/`` (where it is small enough to bring back)
and reduces it with ``scope_times``.
One JSON line, on standard output and in
``chiprun_out/scopes_<workload>.json``:

  scopes        ``scope_times.reduce_file``'s two tables
  setup         ``perf_instrument.setup_phases()``, the per-variant compile
                stats, and the engine's spans after the first units (the
                first call's ``round`` span is trace + lower +
                compile-or-load + one enqueue)
  tracing_cost  rounds a second and seconds a round of the untraced and the
                traced window, and the traced window's device time a round
  by_hand       what one looks at before trusting the reduction: the first
                ``fed:``/``bench:`` host events with their thread, interval
                and stats (the unit's round id), and the metadata of the
                device ops that took most time

A scope is metadata, and jax leaves metadata out of the compile cache's key:
a cache entry compiled before the program had its scopes (or by a checkout
without them) is loaded as it is, and its ops carry the old names. The line
then says ``stale_executable`` and every op reads ``outside``: run again with
``JAX_COMPILATION_CACHE_DIR`` set to an empty directory (PERF.md section 5).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (cells, engine, run, scope_times,  # noqa: E402
                       trace_reduce, xplane)

OUT = os.path.join(ROOT, "chiprun_out")
HOST_EVENTS = 40
DEVICE_OPS = 12
# chiprun_out may bring back 64 MiB: a larger trace is reduced and dropped
KEEP_TRACE_BYTES = 40 * 2 ** 20


def host_events(path: str) -> list[dict]:
    """The first ``fed:``/``bench:`` events of the host plane, by start."""
    import jax

    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(scope_times.SPAN_PREFIXES):
                    events.append({
                        "thread": line.name, "name": ev.name,
                        "start_ms": ev.start_ns * 1e-6,
                        "ms": ev.duration_ns * 1e-6,
                        "stats": dict(ev.stats)})
    events.sort(key=lambda e: e["start_ms"])
    t0 = events[0]["start_ms"] if events else 0.0
    for e in events:
        e["start_ms"] -= t0
    return events[:HOST_EVENTS]


def device_ops(path: str) -> list[dict]:
    """Name, stats and summed time of the ops of the first chip that took
    most time, as the trace's metadata holds them."""
    plane = xplane.read_planes(
        path, lambda n: n.startswith(trace_reduce.DEVICE_PLANE),
        lambda plane, line: line == trace_reduce.OPS_LINE)[0]
    total = {}
    for line in plane["lines"]:
        for mid, s, e in line["events"]:
            total[mid] = total.get(mid, 0.0) + (e - s)
    top = sorted(total, key=total.get, reverse=True)[:DEVICE_OPS]
    return [{"seconds": total[mid],
             "name": plane["metadata"][mid]["name"][:400],
             "stats": {k: str(v)[:400] for k, v in
                       plane["metadata"][mid]["stats"].items()}}
            for mid in top]


def window(driver, seconds: float, annotate=None) -> dict:
    units, elapsed = engine.drive_window(driver, seconds, annotate)
    rounds = len(units) * driver.rounds_per_unit
    return {"rounds": rounds, "elapsed_s": elapsed,
            "rounds_per_s": rounds / elapsed, "s_per_round": elapsed / rounds}


def main() -> int:
    import jax
    from fedml_tpu.obs import perf_instrument

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    cell = cells.load_cell(cells.load_benchmark(), args.workload)
    run.find_chips(cell["chips"])
    data, params, _ = run.prepare(cell, args.seed)
    driver, _ = run.first_units(cell, data, params)
    setup = {"phases": perf_instrument.setup_phases(),
             "variants": perf_instrument.variant_compile_stats(),
             "spans_after_first_units": engine.host_spans(driver.api)}

    untraced = window(driver, run.TRACE_SECONDS)
    tracedir = os.path.join(OUT, f"scopes_{args.workload}")
    shutil.rmtree(tracedir, ignore_errors=True)
    os.makedirs(tracedir)
    with jax.profiler.trace(tracedir):
        traced = window(driver, run.TRACE_SECONDS,
                        jax.profiler.TraceAnnotation)
    path, = glob.glob(os.path.join(tracedir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    scopes = scope_times.reduce_file(path)
    busy = sum(c["busy_s"] for c in scopes["chips"].values()) \
        / len(scopes["chips"])
    traced["device_s_per_round"] = busy / traced["rounds"]
    by_hand = {"host_events": host_events(path), "device_ops": device_ops(path)}
    trace_bytes = os.path.getsize(path)
    if trace_bytes > KEEP_TRACE_BYTES:
        shutil.rmtree(tracedir)
    matched = scopes["matched_by"]
    line = json.dumps({
        "workload": args.workload, "seed": args.seed,
        "stale_executable": not (matched["tf_op"] or matched["name"]),
        "trace_bytes": trace_bytes, "scopes": scopes,
        "setup": setup,
        "tracing_cost": {"untraced": untraced, "traced": traced},
        "by_hand": by_hand})
    print(line, flush=True)
    with open(os.path.join(OUT, f"scopes_{args.workload}.json"), "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
