"""Builds the system under test from a cell's files and drives its window.

This is the only module of the benchmark that imports the program. It takes
from it the engine (``FedAvgAPI``), the task wrapper, the model factory the
configuration names, the compile counters and the engine's own host spans.
"""

from __future__ import annotations

import contextlib
import importlib
import time

import numpy as np

# dispatch units the window keeps undone: the device has the next one queued
MAX_IN_FLIGHT = 2
# the keys of a configuration's file that are FedAvg settings
FED_KEYS = ("client_optimizer", "lr", "wd", "momentum", "batch_size",
            "epochs", "max_batches", "precision")


def resolve(spec: str):
    """The callable that ``module:callable`` names."""
    mod, _, fn = spec.partition(":")
    return getattr(importlib.import_module(mod), fn)


def fed_settings(config: dict, traffic: dict) -> dict:
    """The FedAvg settings of the cell, as the reference reads them too.
    The program's own seed, which draws each round's cohort and shuffles
    its rows, is the traffic's ``sampling_seed``: the same clients arrive
    under every ``--seed``, which changes what they hold and the weights.
    (It has to stay under 4295: ``core/sampling.py`` seeds
    ``numpy.random.RandomState(seed * 1_000_003 + round)``.)"""
    fed = {k: config[k] for k in FED_KEYS if k in config}
    fed.update(traffic.get("fedavg", {}))
    fed["client_num_in_total"] = int(config["population"]["num_clients"])
    fed["client_num_per_round"] = int(traffic["cohort"])
    fed["seed"] = int(traffic["sampling_seed"])
    return fed


def build(config: dict, traffic: dict, data, params, chips: int = 1):
    """The engine of the cell with the benchmark's weights installed. The
    configuration names the model factory and the task wrapper as
    ``module:callable``."""
    import jax
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.local import NetState

    module = resolve(config["model"]["factory"])(
        **config["model"].get("kwargs", {}))
    task = resolve(config["task"])(module)
    fed = fed_settings(config, traffic)
    cfg = FedAvgConfig(comm_round=10 ** 6, frequency_of_the_test=10 ** 9,
                       **fed)
    mesh = None
    if traffic.get("mesh_axes"):
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices()[:chips])
        mesh = Mesh(devs.reshape([-1] + [1] * (len(traffic["mesh_axes"]) - 1)),
                    tuple(traffic["mesh_axes"]))
    api = FedAvgAPI(data, task, cfg, mesh=mesh,
                    **traffic.get("engine", {}))
    api.load_state(NetState(params, {}), api.server_opt_state,
                   jax.random.PRNGKey(fed["seed"]))
    return api


def compile_counters() -> dict:
    from fedml_tpu.obs import perf_instrument as perf

    perf.install()
    return {"compiles": perf.compiles_total(),
            "cache_hits": perf.cache_hits_total(),
            "cache_misses": perf.cache_misses_total()}


def host_spans(api) -> dict:
    """Running totals of the engine's own host spans, in seconds."""
    return dict(api.tracer.totals())


class Driver:
    """Drives one dispatch unit at a time through the window's own call:
    ``run_rounds`` sends a block of ``block_rounds`` rounds as one device
    program, ``run_round`` one round as ``train()`` does. ``unit()`` returns
    the unit's metrics as device arrays without waiting for them."""

    def __init__(self, api, traffic: dict):
        self.api = api
        self.kind = traffic["driver"]
        if self.kind not in ("run_rounds", "run_round"):
            raise ValueError(f"unknown driver {self.kind!r}")
        self.rounds_per_unit = (int(traffic["block_rounds"])
                                if self.kind == "run_rounds" else 1)
        self.next_round = 0

    def unit(self):
        r = self.next_round
        if self.kind == "run_rounds":
            ms = self.api.run_rounds(r, self.rounds_per_unit)
        else:
            ms = self.api.run_round(r)
        self.next_round = r + self.rounds_per_unit
        return ms

    def model(self):
        """The global model after everything dispatched so far, on the host."""
        import jax

        return jax.tree.map(np.asarray, self.api.net.params)


def drive_window(driver: Driver, seconds: float, annotate=None):
    """Dispatch units back to back, at most ``MAX_IN_FLIGHT`` undone, until the
    units already dispatched will outlast ``seconds`` by the pace of the
    last one done; then wait for the last. The device so has the next unit
    queued all through the window, and the window overshoots ``seconds`` by
    less than one unit. Returns the units' metrics and the window's real
    length: the clock stops when the last unit dispatched is ready."""
    import jax

    annotate = annotate or (lambda name: contextlib.nullcontext())
    pending, done = [], []
    t0 = last_done = time.perf_counter()
    unit_s = None
    while True:
        with annotate("bench:dispatch"):
            pending.append(driver.unit())
        while len(pending) >= MAX_IN_FLIGHT:
            with annotate("bench:wait"):
                done.append(jax.block_until_ready(pending.pop(0)))
            now = time.perf_counter()
            unit_s, last_done = now - last_done, now
        if unit_s is not None and (time.perf_counter() - t0
                                   + len(pending) * unit_s) >= seconds:
            break
    with annotate("bench:wait"):
        done.extend(jax.block_until_ready(pending))
    return done, time.perf_counter() - t0
