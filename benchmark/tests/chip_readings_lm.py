"""By hand, on the chip: ``chip_readings.py`` for a cell whose traffic file
has FedAvg settings of its own (``fedavg: {"client_fold": "scan"}``).

    python3 benchmark/tests/chip_readings_lm.py --workload <name> --seeds 11,12,13

``chip_readings.read_seed`` builds its bf16-policy control by REPLACING the
traffic's ``fedavg`` entry, which drops the fold: the cohort is then vmapped
and a model of this size does not fit. Here the policy is added to the entry.
The readings, their names and the output are ``chip_readings.py``'s: the
program, the program under ``precision="bf16"``, the program at the default
matmul precision, each forward of the reference's ``CONTROLS`` (the reference
in bfloat16; top-k taken of the scores without the bias; the weights not
normalised) and half of every batch left out, each put in the program's
place. One JSON line a seed, on standard output and in
``chiprun_out/readings_<workload>.jsonl``.
"""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.tests import chip_readings as cr  # noqa: E402


def read_seed(cell: dict, seed: int) -> dict:
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
    rec["program"] = cr._program(cell, data, init, ref)
    fed = dict(cell["traffic"].get("fedavg", {}), precision="bf16")
    policy = dict(cell, traffic=dict(cell["traffic"], fedavg=fed))
    rec["control_policy_bf16"] = cr._program(policy, data, init, ref)
    jax.config.update("jax_default_matmul_precision", "default")
    rec["control_default_precision"] = cr._program(cell, data, init, ref)
    run.configure_jax(cell["config"])
    for name, forward in cell["reference"].CONTROLS.items():
        low = run.follow_reference(dict(cell, reference=cr._Forward(forward)),
                                   ref_data, init, n_ref)
        rec[f"control_{name}"] = cr._decided(cell, low, ref)
    bad = run.follow_reference(
        cell, ref_data, init, n_ref,
        pack=cr.half_batch(cell["round_reference"].pack_round))
    rec["fault_half_batch"] = cr._decided(cell, bad, ref)
    return rec


def main() -> int:
    cr.read_seed = read_seed  # the rest is chip_readings.py's own
    return cr.main()


if __name__ == "__main__":
    sys.exit(main())
