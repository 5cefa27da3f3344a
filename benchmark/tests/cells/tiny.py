"""Tiny cells for the CPU tests: the full-width models on a handful of
clients and rows. Never run on the chip, never a benchmark cell."""

from __future__ import annotations

import copy
import importlib

from benchmark import cells


def tiny_cell(config: str = "femnist_cnn", driver: str = "run_rounds",
              limits: dict | None = None) -> dict:
    cfg = copy.deepcopy(cells._json("configs", f"{config}.json"))
    pop = cfg["population"]
    if config == "femnist_cnn":
        pop.update(num_clients=12,
                   sizes={"mean": 10, "sigma": 0.5, "min": 3, "max": 16})
        cfg.update(batch_size=4, max_batches=4, reference_client_block=4)
        cohort = 6
    else:
        pop.update(num_clients=2, total_samples=64)
        pop["partition"]["min_size"] = 8
        cfg.update(batch_size=4, max_batches=1, reference_client_block=2)
        cohort = 2
    traffic = {"cohort": cohort, "driver": driver, "block_rounds": 2,
               "engine": ({"device_data": True, "donate": True}
                          if driver == "run_rounds" else {}),
               "check_units": 1 if driver == "run_rounds" else 3,
               "sampling_seed": 17}
    names = ["loss_r0", "loss_r1", "loss_r2", "dparam", "dparam_med", "grad1"]
    return {
        "name": f"tiny_{config}_{driver}", "chips": 1, "config": cfg,
        "traffic": traffic,
        "limits": limits or {"limits": {n: 1e-3 for n in names}},
        "population": importlib.import_module("benchmark.populations.images"),
        "round_reference": importlib.import_module(
            "benchmark.reference.fedavg_round"),
        "counts": importlib.import_module(f"benchmark.counts.{config}"),
        "reference": importlib.import_module(f"benchmark.reference.{config}"),
        "end_to_end": ["rounds_per_s", "samples_per_s", "setup_s"],
        "per_layer": ["pack_ms_per_round", "pad_slots_pct", "train_mfu",
                      "device_busy_ms_per_round", "device_idle_pct",
                      "conv_roofline"],
    }
