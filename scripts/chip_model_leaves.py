"""By hand: the global model after a benchmark cell's first dispatch units,
leaf by leaf, to set two checkouts side by side on one seed. What the cell's
``correct`` cannot see (PERF.md section 7, item 1) shows here: the limits
compare norms of a leaf's change against the reference's, this compares the
change itself against another checkout's.

    # on the chip, from the root of each checkout (the program and the
    # benchmark are the working directory's, whichever file this is):
    python3 <path to>/chip_model_leaves.py dump --workload <cell> --seed <n> \\
        --out chiprun_out/leaves_<tag>.npz
    # anywhere:
    python3 scripts/chip_model_leaves.py compare <a.npz> <b.npz>

``dump`` also prints the first block's losses, the program store's counters,
the set-up split, what the rematerialised blocks keep (``fed_remat_*``) and
the allocator's limit and peaks. A language model's leaves are gigabytes:
give ``--out`` a path outside ``chiprun_out/`` and compare on the chip. ``compare`` prints one JSON line: ``compared`` (the cell's own ``dparam`` and
``dparam_med`` with b in the reference's place), ``leaf_rel`` (for each leaf
the norm of a's model minus b's over the norm of b's change from the shared
weights: worst leaf, its name, the median leaf), the five losses' relative
gaps, and whether the two started from the same weights.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.getcwd())


def dump(workload: str, seed: int, out: str) -> None:
    import jax

    from benchmark import cells, check, run

    cell = cells.load_cell(cells.load_benchmark(), workload)
    run.find_chips(cell["chips"])
    data, params, init = run.prepare(cell, seed)
    _, prog = run.first_units(cell, data, params)
    # float32 as the program holds them (check._leaves widens to float64)
    arrays = {"losses": np.asarray(prog["losses"], np.float64)}
    arrays.update({f"init{k}": v.astype(np.float32)
                   for k, v in check._leaves(init)})
    arrays.update({f"model{k}": v.astype(np.float32) for k, v in
                   check._leaves(prog["models"][max(prog["models"])])})
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    np.savez(out, **arrays)
    # how the block program came to be (PERF.md section 3): the program
    # store's outcomes and seconds from the registry's export (empty in a
    # checkout from before the store), and the set-up split
    from fedml_tpu.obs import perf_instrument as perf
    from fedml_tpu.obs.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    store = {k: v for k, v in snap.items()
             if k.startswith("fed_program_store")}
    # what the rematerialised blocks keep (models/lfm2_moe.py kept_names)
    # beside what the allocator says of the room: empty in a checkout from
    # before the rule, and for a model without such blocks
    remat = {k: v for k, v in snap.items() if k.startswith("fed_remat")}
    mem = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"out": out, "losses": prog["losses"],
                      "program_store": store,
                      "setup_phases": perf.setup_phases(),
                      "conv_sites": perf.conv_sites(), "remat": remat,
                      "memory": {k: mem.get(k) for k in (
                          "bytes_limit", "peak_bytes_in_use",
                          "largest_alloc_size", "bytes_reserved",
                          "peak_bytes_reserved")}}),
          flush=True)


def compare(path_a: str, path_b: str) -> dict:
    from benchmark import check

    a, b = np.load(path_a), np.load(path_b)

    def tree(z, prefix):
        return {k[len(prefix):]: z[k] for k in z.files
                if k.startswith(prefix)}

    init, model_a, model_b = (
        {k: v.astype(np.float64) for k, v in t.items()}
        for t in (tree(b, "init"), tree(a, "model"), tree(b, "model")))
    worst, med = check.leaf_gaps(model_a, model_b, init)
    rel = {k: float(np.linalg.norm(model_a[k] - model_b[k])
                    / max(np.linalg.norm(model_b[k] - init[k]), 1e-30))
           for k in model_b}
    name = max(rel, key=rel.get)
    return {
        "same_init": all(np.array_equal(v, a[f"init{k}"])
                         for k, v in init.items()),
        "leaves_equal_bit_for_bit": sum(
            np.array_equal(a[f"model{k}"], b[f"model{k}"]) for k in model_b),
        "compared": {"dparam": worst, "dparam_med": med},
        "leaf_rel": {"worst": rel[name], "worst_leaf": name,
                     "median": float(np.median(list(rel.values()))),
                     "leaves": len(rel)},
        "loss_rel": [float(abs(x - y) / abs(y))
                     for x, y in zip(a["losses"], b["losses"])],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump")
    d.add_argument("--workload", required=True)
    d.add_argument("--seed", type=int, required=True)
    d.add_argument("--out", required=True)
    c = sub.add_parser("compare")
    c.add_argument("a")
    c.add_argument("b")
    args = ap.parse_args()
    if args.cmd == "dump":
        dump(args.workload, args.seed, args.out)
    else:
        print(json.dumps(compare(args.a, args.b)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
