"""By hand, on the chip: the runs the bounds are set from, made as the
driver makes them (one process a run; this parent never touches jax).

    python3 benchmark/tests/chip_sets.py --workload <name> --seeds a,b,c,d,e,f \\
        [--sets 2] [--trace-seeds x,y,z] [--seconds <run_seconds>]

Each set runs every seed once with ``--trace 0``; then one ``--trace 1`` run
for each trace seed. Every result line goes to
``chiprun_out/sets_<workload>.jsonl``; at the end, for each metric, the
median and the quartile spread (``statistics.quantiles(n=4)``, as a share of
the median) of each set, and whether every run was ``correct``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def one_run(workload, seed, seconds, trace):
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=1500)
    wall = time.time() - t0
    marks = [ln for ln in p.stderr.splitlines() if ln.startswith("bench[")]
    if p.returncode != 0 or not p.stdout.strip():
        print(p.stderr[-3000:], file=sys.stderr)
        return {"rc": p.returncode, "wall_s": wall, "marks": marks}
    res = json.loads(p.stdout.strip().splitlines()[-1])
    return {"rc": 0, "wall_s": wall, "marks": marks, "result": res}


def spread(values):
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q[2] - q[0]) / med


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=int, default=None)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    log = os.path.join(out, f"sets_{args.workload}.jsonl")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    sets, all_ok = [], True
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            rec = one_run(args.workload, seed, seconds, 0)
            rec.update(set=k + 1, seed=seed, trace=0)
            with open(log, "a") as f:
                f.write(json.dumps(rec) + "\n")
            ok = rec["rc"] == 0 and rec["result"]["correct"]
            all_ok &= ok
            print(f"set {k + 1} seed {seed} rc {rec['rc']} correct {ok} "
                  f"wall {rec['wall_s']:.1f}s "
                  + (json.dumps({m: v["value"] for m, v in
                                 rec["result"]["metrics"].items()})
                     if rec["rc"] == 0 else ""), flush=True)
            if rec["rc"] == 0:
                rows.append(rec["result"])
        sets.append(rows)
    for seed in (int(s) for s in args.trace_seeds.split(",") if s):
        rec = one_run(args.workload, seed, seconds, 1)
        rec.update(set=0, seed=seed, trace=1)
        with open(log, "a") as f:
            f.write(json.dumps(rec) + "\n")
        ok = rec["rc"] == 0 and rec["result"]["correct"]
        all_ok &= ok
        print(f"trace seed {seed} rc {rec['rc']} correct {ok} wall "
              f"{rec['wall_s']:.1f}s "
              + (json.dumps({"metrics": {m: v["value"] for m, v in
                                         rec["result"]["metrics"].items()},
                             "device": rec["result"]["device"]})
                 if rec["rc"] == 0 else ""), flush=True)
    for k, rows in enumerate(sets):
        if len(rows) < 2:
            continue
        for m in rows[0]["metrics"]:
            vals = [r["metrics"][m]["value"] for r in rows]
            if m == "setup_s":
                vals = vals[1:] if k == 0 else vals  # the first run compiles
            med, spr = spread(vals) if len(vals) > 1 else (vals[0], 0.0)
            print(f"set {k + 1} {m}: median {med!r} spread {spr:.5f} "
                  f"({len(vals)} runs)")
    print("all correct:", all_ok)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
