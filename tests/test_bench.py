"""bench.py and chip_smoke.py: the measurers emit well-formed JSON on
whatever backend jax has, and the commands that claim a chip number
(``python bench.py``, ``python chip_smoke.py``) refuse to run without a TPU:
non-zero exit, no result line, no fallback."""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import_bench():
    sys.modules.pop("bench", None)
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    import bench

    return bench


@pytest.fixture()
def tiny_bench_env(monkeypatch):
    """Shrink the flagship config to test scale via bench's env knobs."""
    monkeypatch.setenv("FEDML_BENCH_BLOCK", "2")
    monkeypatch.setenv("FEDML_BENCH_ROUNDS", "2")
    monkeypatch.setenv("FEDML_BENCH_ROUNDS_CHEAP", "2")
    monkeypatch.setenv("FEDML_BENCH_CLIENTS_PER_ROUND", "2")
    monkeypatch.setenv("FEDML_BENCH_MAX_BATCHES", "2")

    import fedml_tpu.data.registry as registry
    from fedml_tpu.data.synthetic import synthetic_images

    def tiny_load(name, **kw):
        assert name == "femnist"
        return synthetic_images(
            num_clients=3400, image_shape=(28, 28, 1), num_classes=62,
            samples_per_client=4, test_samples=8, seed=0,
            size_lognormal=False, as_uint8=True)

    monkeypatch.setattr(registry, "load_dataset", tiny_load)


def _measure_and_parse(mode, capsys):
    bench = _import_bench()
    bench._measure(mode)
    out = [l for l in capsys.readouterr().out.strip().splitlines()
           if l.startswith("{")]
    # a leg may print an early coarser line; the LAST JSON line is the
    # authoritative result (bench.py module docstring) and every line must
    # parse
    assert 1 <= len(out) <= 2, out
    for line in out:
        json.loads(line)
    rec = json.loads(out[-1])
    assert rec["metric"] == "fedavg_femnist_rounds_per_sec"
    assert rec["value"] > 0 and rec["unit"] == "rounds/sec"
    assert rec["samples_per_sec_per_chip"] > 0
    assert rec["mode"] == mode
    return rec


def test_measure_block_emits_json(tiny_bench_env, capsys):
    _measure_and_parse("block", capsys)


def test_mfu_estimate_tpu_only(monkeypatch):
    """MFU rides the result only for TPU runs with a RECOGNIZED device
    generation (ADVICE r4: a guessed peak silently misreports on v2/v3/
    v6e) and scales linearly with samples/sec."""
    import types

    bench = _import_bench()
    cpu = bench._result(10.0, "block", 1000.0, 1, "cpu")
    assert "mfu_vs_bf16_peak" not in cpu

    class _Dev:
        device_kind = "TPU v5e"

    monkeypatch.setitem(sys.modules, "jax",
                        types.SimpleNamespace(devices=lambda: [_Dev()]))
    tpu = bench._result(10.0, "block", 1000.0, 1, "tpu")
    expect = 1000.0 * 3 * bench._CNN_FWD_FLOPS / 1.97e14
    assert tpu["mfu_vs_bf16_peak"] == round(expect, 5)  # stored rounded
    assert 0 < tpu["mfu_vs_bf16_peak"] < 1
    # v6e quotes against the Trillium peak, not the v5e default
    _Dev.device_kind = "TPU v6 lite"
    v6 = bench._result(10.0, "block", 1000.0, 1, "tpu")
    assert v6["mfu_vs_bf16_peak"] == round(expect * 1.97e14 / 9.18e14, 5)
    # unknown generation: omit the field rather than guess a peak
    _Dev.device_kind = "TPU v99x"
    assert "mfu_vs_bf16_peak" not in bench._result(10.0, "block", 1000.0, 1,
                                                   "tpu")


def test_measure_per_round_emits_json(tiny_bench_env, capsys):
    _measure_and_parse("per_round", capsys)


def test_bench_main_without_tpu_exits_nonzero_with_no_result(monkeypatch,
                                                             capsys):
    """``python bench.py`` measures in its own process on the TPU it finds;
    on the CPU it must exit non-zero and print no result line."""
    bench = _import_bench()
    for k in list(os.environ):
        if k.startswith("FEDML_BENCH_"):
            monkeypatch.delenv(k)
    monkeypatch.setattr(bench, "_measure", lambda mode: pytest.fail(
        f"bench.main() measured {mode!r} without a TPU"))
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


def test_chip_smoke_without_tpu_exits_nonzero_with_no_ok_line():
    """The chip check's first leg: in a sandbox without an accelerator the
    smoke exits non-zero and prints no ``ok`` line (it never sets
    JAX_PLATFORMS and never retries on another backend)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_bench_longctx_one_point(monkeypatch, capsys):
    """bench_longctx sweep: one tiny point per impl prints well-formed
    records with matching losses (flash ≡ dense math)."""
    sys.modules.pop("bench_longctx", None)
    scripts = os.path.join(REPO_ROOT, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import bench_longctx

    monkeypatch.setattr(
        sys, "argv",
        ["bench_longctx.py", "--seqs", "64", "--flash", "2", "--batch", "1",
         "--dim", "16", "--depth", "1", "--heads", "2", "--vocab", "32",
         "--steps", "1"])
    bench_longctx.main()
    out = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()
           if l.startswith("{")]
    assert [r["impl"] for r in out] == ["flash", "dense"]
    for r in out:
        assert "error" not in r, r
        assert r["tokens_per_sec"] > 0
    assert abs(out[0]["loss"] - out[1]["loss"]) < 1e-3


def test_bench_scaling_one_point(tiny_bench_env, monkeypatch, capsys):
    """bench_scaling sweep: one tiny femnist point through the working-set
    block plane prints a well-formed record (keeps the scaling study
    runnable, not just bench.py)."""
    sys.modules.pop("bench_scaling", None)
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    import bench_scaling

    monkeypatch.setattr(
        sys, "argv",
        ["bench_scaling.py", "--workload", "femnist_cnn", "--points", "2",
         "--rounds", "1", "--batch_size", "4", "--max_batches", "1",
         "--working_set", "1"])  # opt-in since ADVICE r2 #2 (default is
    #                             full_park for sweep comparability)
    bench_scaling.main()
    out = [l for l in capsys.readouterr().out.strip().splitlines()
           if l.startswith("{")]
    assert len(out) == 1
    rec = json.loads(out[0])
    assert "error" not in rec, rec
    assert rec["clients_per_round"] == 2
    assert rec["rounds_per_sec"] > 0
    assert rec["data_plane"] == "working_set"
    assert rec["span_seconds"]["host_pack"] >= 0
