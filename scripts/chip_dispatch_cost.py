"""By hand, on the chip: what one dispatch of a cell's block program costs
the host, outside any profiler (``dispatch_ms_per_round`` comes from traced
runs, two dispatches each).

    # from the root of a checkout (the program and the benchmark are the
    # working directory's, whichever file this is):
    python3 <path to>/chip_dispatch_cost.py --workload <cell> --seed <n>

Builds the cell's engine as ``benchmark/run.py`` does, drives two units, then
times ``_dispatch_block`` on one placed block: 12 calls each waited for, 12
with one more queued behind. Prints one JSON line: every call's milliseconds,
the two medians, and how many of the 24 calls took jit's Python path
(``ExecuteReplicated.__call__``: 0 where the C++ fast path serves them).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.getcwd())

CALLS = 12


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()

    import jax
    from jax._src.interpreters import pxla

    from benchmark import cells, run

    python_calls = [0]
    execute = pxla.ExecuteReplicated.__call__

    def counted(self, *a, **k):
        python_calls[0] += 1
        return execute(self, *a, **k)

    pxla.ExecuteReplicated.__call__ = counted
    cell = cells.load_cell(cells.load_benchmark(), args.workload)
    run.find_chips(cell["chips"])
    data, params, _ = run.prepare(cell, args.seed)
    driver, _ = run.first_units(cell, data, params)
    api = driver.api
    jax.block_until_ready(driver.unit())
    placed = api._place_block(api._pack_block_host(
        driver.next_round, driver.rounds_per_unit))[1]
    jax.block_until_ready(placed)

    python_calls[0] = 0
    alone, queued, pending = [], [], []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        ms = api._dispatch_block(placed)
        alone.append(time.perf_counter() - t0)
        jax.block_until_ready(ms)
    for _ in range(CALLS):
        t0 = time.perf_counter()
        pending.append(api._dispatch_block(placed))
        queued.append(time.perf_counter() - t0)
        if len(pending) >= 2:
            jax.block_until_ready(pending.pop(0))
    jax.block_until_ready(pending)
    print(json.dumps({
        "tree": os.getcwd(), "rounds_per_dispatch": driver.rounds_per_unit,
        "python_path_calls": python_calls[0],
        "alone_ms": [round(1e3 * t, 3) for t in alone],
        "queued_ms": [round(1e3 * t, 3) for t in queued],
        "median_alone_ms": round(1e3 * float(np.median(alone)), 3),
        "median_queued_ms": round(1e3 * float(np.median(queued)), 3),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
