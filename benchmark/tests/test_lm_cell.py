"""The language-model cell's own files at a size the CPU holds: the token
population, the counts against hand counts, whole runs through ``run_cell``
(a sound run is ``correct``, each planted fault is not), and the reader of
``moe_pad_rows_pct``."""

import io

import jax
import numpy as np
import pytest

from benchmark import cells, run
from benchmark.counts import lfm2_24b_a2b_ep8 as counts
from benchmark.layer_metrics import moe_pad_rows_pct
from benchmark.populations import tokens
from benchmark.reference import lfm2_24b_a2b_ep8 as ref
from benchmark.tests.cells.tiny_lm import SMALL, tiny_cell
from benchmark.tests.test_correct import (PEAKS,  # noqa: F401
                                          _no_compile_cache)

SPEC = {"num_clients": 4, "vocab_size": 512, "seq_len": 64,
        "sequences_per_client": 16, "topics": 16, "zipf_exponent": 1.1,
        "dirichlet_alpha": 0.5, "test_sequences": 3}


# ------------------------------------------------------------ population
def test_token_population_shapes_and_ids():
    data = tokens.make(SPEC, 2 ** 31 + 5)
    assert data.train_x.shape == data.train_y.shape == (64, 64)
    assert data.train_x.dtype == np.int32 and data.num_clients == 4
    assert data.train_x.min() >= 1 and data.train_y.max() <= 511
    # labels are the sequence one token on
    np.testing.assert_array_equal(data.train_x[:, 1:], data.train_y[:, :-1])
    assert [len(v) for v in data.train_idx_map.values()] == [16] * 4
    assert data.test_x.shape == (3, 64)


def test_token_population_follows_the_seed_and_the_silo():
    a, b = tokens.make(SPEC, 11), tokens.make(SPEC, 12)
    np.testing.assert_array_equal(a.train_x, tokens.make(SPEC, 11).train_x)
    assert (a.train_x != b.train_x).mean() > 0.5

    def unigram(data, k):
        return np.bincount(data.train_x[data.train_idx_map[k]].ravel(),
                           minlength=512) / (16 * 64)

    # non-IID: a silo's unigram is not its neighbour's (total variation)
    gaps = [0.5 * np.abs(unigram(a, k) - unigram(a, k + 1)).sum()
            for k in range(3)]
    assert min(gaps) > 0.1, gaps


# ---------------------------------------------------------------- counts
def test_counts_by_hand_at_the_cells_sizes():
    sz = counts.SIZES
    d = 2048
    conv = d * 3 * d + d * d
    attn = 2 * d * d + 2 * d * 512 + 2 * 32 * 64 * 2049 / 2
    dense = 3 * d * 11776
    experts = d * 64 + 0.5 * 3 * d * 1536
    macs = 8192 * d + (conv + dense) + (attn + experts) + 3 * (conv + experts)
    assert counts.forward_flops_per_sample() == 2 * macs
    assert counts.held_share(sz) == 0.5
    assert counts.budget_rows(sz, 8 * 2048) == 12288


def test_step_ops_by_hand():
    ops = {n: (f, b) for n, f, b in counts.matmul_ops_per_step(8)}
    tokens_, d = 8 * 2048, 2048
    # a matrix in a rematerialised block: forward twice, two gradients
    assert ops["layer_0.w2"] == (
        4 * 2.0 * tokens_ * 11776 * d,
        4 * 4.0 * (tokens_ * 11776 + 11776 * d + tokens_ * d))
    assert ops["head"][0] == 3 * 2.0 * tokens_ * d * 8192
    # the expert layer at its row budget: 48 tiles of 256 rows, three
    # products forward twice and six backward, each with its own matrix
    assert ops["layer_2.experts"] == (
        12 * 48 * 2.0 * 256 * d * 1536,
        12 * 48 * 4.0 * (256 * d + d * 1536 + 256 * 1536))
    # the last of eight query blocks sees every key; ten products
    f, _ = ops["layer_1.attend@1792"]
    assert f == 10 * 2.0 * (8 * 8) * (4 * 256) * 64 * 2048
    assert sum(n.startswith("layer_1.attend") for n in ops) == 8
    assert "layer_1.experts" in ops and "layer_0.experts" not in ops


def test_counts_at_other_sizes():
    small = dict(SMALL)
    ops = counts.matmul_ops_per_step(2, small, 32)
    names = [n for n, _, _ in ops]
    assert names.count("layer_2.q_proj") == 1 and "layer_1.w1" in names
    assert counts.forward_flops_per_sample(small, 32) > 0


# ------------------------------------------------------------ whole runs
def _run(cell, seed=2 ** 31 + 11):
    return run.run_cell(cell, seed, 0.2, False, devs=jax.devices()[:1],
                        peaks=PEAKS, out=io.StringIO())


def test_sound_run_is_correct():
    res = _run(tiny_cell())
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == {"loss_r0", "loss_r1", "dparam",
                                    "dparam_med"}
    assert set(res["metrics"]) == {"rounds_per_s", "samples_per_s", "setup_s"}
    assert res["attempted"] % 2 == 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [{"select_with_bias": False},
                                   {"normalise": False}])
def test_fault_planted_in_the_routing_is_not_correct(fault):
    """The reference with the fault stands in for the plain one: the
    program, which routes as published, then reads as the one at fault."""
    res = _run(tiny_cell(forward=ref.make(SMALL, **fault)[1]))
    assert not res["correct"], res["compared"]


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


def test_control_bf16_is_not_correct():
    cell = tiny_cell()
    cell["traffic"]["fedavg"]["precision"] = "bf16"
    res = _run(cell)
    assert not res["correct"], res["compared"]


# ---------------------------------------------------------------- reader
def test_reader_on_a_made_up_counter_and_on_none(monkeypatch):
    from fedml_tpu.obs import perf_instrument

    monkeypatch.setattr(perf_instrument, "moe_rows",
                        lambda: {"real": 8192.0, "dispatched": 12288.0})
    assert moe_pad_rows_pct.read({}) == pytest.approx(100 / 3)
    monkeypatch.setattr(perf_instrument, "moe_rows",
                        lambda: {"real": 0.0, "dispatched": 0.0})
    assert moe_pad_rows_pct.read({}) is None
    monkeypatch.delattr(perf_instrument, "moe_rows")  # the parent's program
    assert moe_pad_rows_pct.read({}) is None
    meta = next(m for m in cells.load_benchmark()["per_layer"]
                if m["name"] == "moe_pad_rows_pct")
    assert (moe_pad_rows_pct.UNIT, moe_pad_rows_pct.LAYER,
            moe_pad_rows_pct.MOVES) == (meta["unit"], meta["layer"],
                                        meta["moves"])
