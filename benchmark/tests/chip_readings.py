"""By hand, on the chip: the readings that the limits of ``correct`` are set
from, at the cell's own size, several seeds in one process.

    python3 benchmark/tests/chip_readings.py --workload <name> --seeds 11,12,13

For each seed, every reading taken against the plain reference over the
cell's first rounds and put through ``check.decide`` with the cell's limits:

  program                   the program as the configuration states it (the
                            LOWER reading; has to come out correct)
  control_policy_bf16       the program with FedAvgConfig(precision="bf16")
  control_default_precision the program at the TPU's default matmul
                            precision (one bf16 pass)
  control_<name>            each lowered forward of the configuration's
                            reference (``CONTROLS``) put in the program's place
  fault_half_batch          the second half of every batch left out, the mean
                            taken over the rest, planted in the reference put
                            in the program's place

Every control and fault has to come out NOT correct. A state left unchanged
reads 1 by construction and needs no run. One JSON line a seed, on standard
output and in ``chiprun_out/readings_<workload>.jsonl``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, check, run  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out")


def half_batch(pack):
    """``pack`` with the second half of every batch left out: the loss is
    the mean over the rest."""
    def packed(*args):
        idx, mask, nsamp = pack(*args)
        mask = mask.copy()
        mask[:, :, mask.shape[2] // 2:] = 0.0
        return idx, mask, nsamp
    return packed


def _decided(cell, got, ref):
    # a reference put in the program's place keeps, as the program does,
    # the model after each dispatch unit and no other
    units = int(cell["traffic"].get("block_rounds", 1))
    got = dict(got, models={n: m for n, m in got["models"].items()
                            if n % units == 0})
    correct, compared = check.decide(check.numbers(got, ref), cell["limits"])
    return {"correct": correct,
            "numbers": {k: c["value"] for k, c in compared.items()}}


def _program(cell, data, init, ref):
    import jax

    driver, prog = run.first_units(cell, data,
                                   jax.tree.map(jax.numpy.asarray, init))
    del driver
    gc.collect()
    return _decided(cell, prog, ref)


def read_seed(cell: dict, seed: int) -> dict:
    """All the readings of one seed, as the module's text lists them."""
    import jax

    data, _, init = run.prepare(cell, seed)
    ref_data = (data.train_x, data.train_y, data.train_idx_map)
    n_ref = int(cell["traffic"]["check_units"]) * int(
        cell["traffic"].get("block_rounds", 1))
    t0 = time.perf_counter()
    ref = run.follow_reference(cell, ref_data, init, n_ref)
    rec = {"workload": cell["name"], "seed": seed,
           "reference_s": time.perf_counter() - t0,
           "reference_losses": ref["losses"]}
    rec["program"] = _program(cell, data, init, ref)
    policy = dict(cell, traffic=dict(cell["traffic"],
                                     fedavg={"precision": "bf16"}))
    rec["control_policy_bf16"] = _program(policy, data, init, ref)
    jax.config.update("jax_default_matmul_precision", "default")
    rec["control_default_precision"] = _program(cell, data, init, ref)
    run.configure_jax(cell["config"])
    for name, forward in getattr(cell["reference"], "CONTROLS", {}).items():
        low = run.follow_reference(dict(cell, reference=_Forward(forward)),
                                   ref_data, init, n_ref)
        rec[f"control_{name}"] = _decided(cell, low, ref)
    bad = run.follow_reference(cell, ref_data, init, n_ref,
                               pack=half_batch(cell["round_reference"].pack_round))
    rec["fault_half_batch"] = _decided(cell, bad, ref)
    return rec


class _Forward:
    """Stands in for a reference module whose forward a control lowered."""

    def __init__(self, forward):
        self.forward = forward


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = cells.load_cell(cells.load_benchmark(), args.workload)
    run.find_chips(cell["chips"])
    os.makedirs(OUT, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = json.dumps(read_seed(cell, seed))
        print(line, flush=True)
        with open(os.path.join(OUT, f"readings_{args.workload}.jsonl"),
                  "a") as f:
            f.write(line + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
