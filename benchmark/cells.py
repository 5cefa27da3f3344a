"""Finds a cell's files by the names ``BENCHMARK.json`` gives it.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Everything that belongs to one configuration, one mix, one cell or one
per-layer metric sits in a file of its own, so that a later change adds
files and entries and edits nothing:

  configs/<config>.json          sizes, model factory, task wrapper, FedAvg
                                 settings; names its population generator
                                 and its round reference
  populations/<generator>.py     population from the seed: ``make(spec, seed)``
  counts/<config>.py             operations and bytes from shapes
  reference/<config>.py          the plain float32 forward and its weights
  reference/<round_reference>.py the plain federated round: ``run_rounds``
  traffic/<traffic>.json         cohort, driver, block length, engine options
  limits/<workload>.json         the limits of ``correct`` and their readings
  layer_metrics/<metric>.py      one reader per per-layer metric
  end_to_end/<metric>.py         one reader per end-to-end metric
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_names(bench: dict, group: str, workload: str) -> list[str]:
    """The metrics of ``group`` that this cell reports."""
    return [m["name"] for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_cell(bench: dict, workload: str) -> dict:
    """The cell's resolved files; raises KeyError for an unknown name."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = entry["config"]
    cfg = _json("configs", f"{config}.json")
    return {
        "name": workload,
        "chips": int(entry["chips"]),
        "config": cfg,
        "population": importlib.import_module(
            f"benchmark.populations.{cfg['population']['generator']}"),
        "round_reference": importlib.import_module(
            f"benchmark.reference.{cfg['round_reference']}"),
        "traffic": _json("traffic", f"{entry['traffic']}.json"),
        "limits": _json("limits", f"{workload}.json"),
        "counts": importlib.import_module(f"benchmark.counts.{config}"),
        "reference": importlib.import_module(f"benchmark.reference.{config}"),
        "end_to_end": metric_names(bench, "end_to_end", workload),
        "per_layer": metric_names(bench, "per_layer", workload),
    }


_READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def metric_reader(group: str, name: str):
    """The reader module of one metric: ``UNIT`` and ``read(run)``, which
    returns None where it finds nothing to read."""
    return importlib.import_module(
        f"benchmark.{_READER_DIRS[group]}.{name}")


def load_peaks(device_kind: str) -> dict:
    peaks = _json("peaks.json")
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return peaks[device_kind]
