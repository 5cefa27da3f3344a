"""The readers of the round program's own count of its matrix work,
``matmul_roofline`` and ``recompute_flops_pct``: nothing without a trace
or without counts (the parent's program has no ``matmul_ops``), and the
formula's value on counts planted in the program's place."""

import pytest

from benchmark import cells
from benchmark.layer_metrics import matmul_roofline, recompute_flops_pct
from benchmark.tests.test_correct import PEAKS

# a round's products: 2 ops at the FLOPs bound (2e11 FLOPs each, 1.02 ms
# at the peak), one at the bytes bound (8.19e11 bytes, 1 s): its 1e11 FLOPs
# are made again
PLANTED = [("fed_delta_rule", "forward", 2e11, 1e8),
           ("fed_delta_rule", "recompute", 1e11, 8.19e11),
           ("outside", "gradient", 2e11, 1e8)]


def _run(trace=True):
    return {"rounds": 4, "chips": 1, "peaks": PEAKS,
            "trace": {"conv_s": 20.0} if trace else None}


def test_without_a_trace_or_counts_nothing_is_read(monkeypatch):
    from fedml_tpu.obs import perf_instrument

    monkeypatch.setattr(perf_instrument, "matmul_ops", lambda: PLANTED)
    assert matmul_roofline.read(_run(trace=False)) is None
    assert matmul_roofline.read(dict(_run(), peaks=None)) is None
    assert matmul_roofline.read(dict(_run(), trace={"conv_s": 0.0})) is None
    monkeypatch.setattr(perf_instrument, "matmul_ops", lambda: [])
    assert matmul_roofline.read(_run()) is None
    assert recompute_flops_pct.read(_run()) is None
    monkeypatch.delattr(perf_instrument, "matmul_ops")  # the parent's program
    assert matmul_roofline.read(_run()) is None
    assert recompute_flops_pct.read(_run()) is None


def test_formulas_on_planted_counts(monkeypatch):
    from fedml_tpu.obs import perf_instrument

    monkeypatch.setattr(perf_instrument, "matmul_ops", lambda: PLANTED)
    least = 4 * (2e11 / 1.97e14 + 1.0 + 2e11 / 1.97e14)
    assert matmul_roofline.least_seconds(_run(), PLANTED) == \
        pytest.approx(least)
    assert matmul_roofline.read(_run()) == pytest.approx(100 * least / 20.0)
    assert matmul_roofline.read(dict(_run(), chips=4)) == \
        pytest.approx(25 * least / 20.0)
    assert recompute_flops_pct.read(_run()) == pytest.approx(20.0)
    assert recompute_flops_pct.read({}) == pytest.approx(20.0)  # untraced


def test_entries_name_the_readers():
    bench = cells.load_benchmark()
    for reader in (matmul_roofline, recompute_flops_pct):
        meta = next(m for m in bench["per_layer"] if m["name"] == reader.NAME)
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == (
            meta["unit"], meta["layer"], meta["moves"])
        # the ResNet cell's count of issued convolutions is no measure of
        # its useful products, and it rematerialises nothing
        assert meta["workloads"] == [
            "lfm2_24b_a2b_ep8_silo4_seq2k_block",
            "qwen3_next_80b_a3b_ep16_silo4_seq8k_block"]
