"""``correct`` comes out true for a sound run and false for the control and
for each fault a training cell can have, at a size the CPU holds.

The runs skip the harness's look for a chip (``run_cell`` is handed the CPU
device) and drive the rest of a run: population, weights, engine, first
rounds through the window's own call, a short window, the plain reference,
the comparison. The ResNet runs are held to the chip cell's own limits
(``limits/cifar_resnet56_silo10_block.json``); the CNN runs, whose
configuration has no cell yet, to 1e-3 on every number (the CPU follows the
reference to 1e-6). On the chip the controls and the faults were read at the
cell's own size (PERF.md section 2).
"""

import io
import json

import jax
import numpy as np
import pytest

from benchmark import cells, run
from benchmark.tests.cells.tiny import tiny_cell

PEAKS = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}
LIMITS = cells._json("limits", "cifar_resnet56_silo10_block.json")


def _run(cell, seed=2 ** 31 + 11):
    out = io.StringIO()
    res = run.run_cell(cell, seed, 0.2, False, devs=jax.devices()[:1],
                       peaks=PEAKS, out=out)
    assert json.loads(out.getvalue().splitlines()[-1])["correct"] \
        == res["correct"]
    assert list(res)[-1] == "compared"
    return res


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # entries written for another machine's CPU only print warnings here
    jax.config.update("jax_enable_compilation_cache", False)
    yield


@pytest.mark.parametrize("config,limits", [("femnist_cnn", None),
                                           ("cifar_resnet56", LIMITS)])
def test_sound_run_is_correct(config, limits):
    res = _run(tiny_cell(config, limits=limits))
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"rounds_per_s", "samples_per_s", "setup_s"}
    assert res["attempted"] % 2 == 0 and res["failed"] == 0


def test_per_round_driver_is_correct_and_reads_first_gradient():
    lim = {"limits": {k: 1e-4 for k in ("loss_r0", "loss_r1", "loss_r2",
                                        "dparam", "dparam_med", "grad1")}}
    res = _run(tiny_cell(driver="run_round", limits=lim))
    assert res["correct"], res["compared"]
    assert "grad1" in res["compared"]


@pytest.mark.parametrize("config,limits", [("femnist_cnn", None),
                                           ("cifar_resnet56", LIMITS)])
def test_control_bf16_is_not_correct(config, limits):
    cell = tiny_cell(config, limits=limits)
    cell["traffic"]["fedavg"] = {"precision": "bf16"}
    res = _run(cell)
    assert not res["correct"], res["compared"]


def test_fault_state_returned_unchanged(monkeypatch):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    orig = FedAvgAPI.run_rounds

    def unchanged(self, start, n):
        kept = jax.tree.map(np.asarray, self.net)
        ms = orig(self, start, n)
        self.net = jax.tree.map(jax.numpy.asarray, kept)
        return ms

    monkeypatch.setattr(FedAvgAPI, "run_rounds", unchanged)
    res = _run(tiny_cell())
    assert not res["correct"]
    assert res["compared"]["dparam"]["value"] == pytest.approx(1.0)


def test_fault_half_of_each_batch_left_out(monkeypatch):
    from fedml_tpu.algorithms import fedavg

    orig = fedavg.pack_client_indices

    def half(*a, **kw):
        ib = orig(*a, **kw)
        ib.mask[:, :, ib.mask.shape[2] // 2:] = 0.0
        return ib

    monkeypatch.setattr(fedavg, "pack_client_indices", half)
    res = _run(tiny_cell())
    assert not res["correct"], res["compared"]


def test_missing_limit_is_not_correct():
    res = _run(tiny_cell(limits={"limits": {"loss_r0": 1e-3}}))
    assert not res["correct"]
    assert res["compared"]["dparam"]["limit"] == "missing"


def test_a_compile_inside_the_window_is_an_error(monkeypatch):
    from benchmark import engine

    orig = engine.Driver.unit
    fresh = iter(range(2, 100))

    def unit_that_compiles(self):
        if self.next_round >= 2:      # past the first units: in the window
            n = next(fresh)
            jax.jit(lambda x: x * n)(jax.numpy.ones(n)).block_until_ready()
        return orig(self)

    monkeypatch.setattr(engine.Driver, "unit", unit_that_compiles)
    with pytest.raises(RuntimeError, match="compiled inside the window"):
        _run(tiny_cell())
