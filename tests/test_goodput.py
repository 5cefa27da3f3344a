"""Round economics: goodput & duty-cycle accounting, the per-variant
compile observatory, and the longitudinal run-store.

Pins (docs/PERFORMANCE.md §Round economics, docs/OBSERVABILITY.md):

- the injected-clock decomposition oracle: buckets are exclusive, clip in
  priority order, and sum to the wall EXACTLY — over-reported spans can
  never push the sum past the wall;
- the span->bucket mapping: sync rounds count pack as the prefetch stall
  and h2d on the wall; pipelined rounds count only the stall (pack/h2d
  overlapped on the prefetch thread);
- a seeded chaos straggle on the loopback wire moves exactly the
  wire_wait bucket — the forensic attribution the run-store diff names;
- a process with no round program counted is graceful (duty-cycle-only
  blocks, never a raise); MFU appears only when the device kind resolves
  a peak, and counts the program's forward and gradient products;
- instrumentation OFF is bitwise identical: model bits (standalone +
  pipelined) and wire bytes (loopback sim) match a telemetry-on twin;
- every new family pre-registers at zero (fed_duty_cycle{bucket},
  fed_goodput_*, fed_xla_variant_*) so 'no goodput yet' reads 0, not as
  a missing family;
- the run-store: ingest (events + BENCH blobs, sha dedupe, headerless
  historical blobs), diff (names the moved bucket), trend, and the
  bench_gate hook over the flattened summary;
- report.py / fedtop columns hide ('-') on logs and digests that predate
  the fields.
"""

import json
import os

import numpy as np
import pytest

import jax

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_lr
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import goodput
from fedml_tpu.obs import perf_instrument as perf
from fedml_tpu.obs.metrics import REGISTRY, MetricsRegistry
from fedml_tpu.obs.provenance import provenance, stamp
from fedml_tpu.obs.telemetry import Telemetry


@pytest.fixture(scope="module")
def lr_data():
    return synthetic_lr(num_clients=6, dim=12, num_classes=4, seed=0)


@pytest.fixture(scope="module")
def lr_task():
    return classification_task(LogisticRegression(num_classes=4))


def _cfg(rounds=3, **kw):
    kw.setdefault("comm_round", rounds)
    kw.setdefault("client_num_in_total", 6)
    kw.setdefault("client_num_per_round", 3)
    kw.setdefault("batch_size", 8)
    kw.setdefault("lr", 0.1)
    kw.setdefault("max_batches", 2)
    kw.setdefault("frequency_of_the_test", 100)
    return FedAvgConfig(**kw)


def _leaves(api):
    return [np.asarray(x) for x in jax.tree.leaves(api.net.params)]


# ------------------------------------------------- decomposition oracle
def test_decompose_sums_to_wall_exactly():
    """Injected clocks: arbitrary measured phases, sum == wall always."""
    b = goodput.decompose(1.0, compute=0.4, h2d=0.05, prefetch_stall=0.1,
                          wire_wait=0.2, agg_flush=0.05)
    assert set(b) == set(goodput.BUCKETS)
    assert sum(b.values()) == pytest.approx(1.0, abs=1e-12)
    assert b["compute"] == pytest.approx(0.4)
    assert b["drain"] == pytest.approx(0.2)


def test_decompose_clips_overreported_spans():
    """Overlapping/over-reported spans clip in priority order: the total
    can never exceed the wall and drain never goes negative."""
    b = goodput.decompose(0.5, compute=0.4, h2d=0.3, prefetch_stall=0.2)
    assert sum(b.values()) == pytest.approx(0.5, abs=1e-12)
    assert b["compute"] == pytest.approx(0.4)
    assert b["h2d"] == pytest.approx(0.1)  # clipped at the remaining wall
    assert b["prefetch_stall"] == 0.0
    assert b["drain"] == 0.0
    # degenerate walls stay sane
    z = goodput.decompose(0.0, compute=1.0)
    assert sum(z.values()) == 0.0
    n = goodput.decompose(-1.0, compute=1.0)
    assert sum(n.values()) == 0.0


def test_buckets_from_spans_sync_vs_pipelined():
    """Sync: pack IS the stall, h2d on the wall. Pipelined: only the
    stall counts (pack/h2d overlapped on the prefetch thread)."""
    spans = {"pack": 0.1, "h2d": 0.05, "round": 0.2, "prefetch_stall": 0.03}
    sync = goodput.buckets_from_spans(1.0, spans, compute_wait_s=0.1)
    assert sync["prefetch_stall"] == pytest.approx(0.1)
    assert sync["h2d"] == pytest.approx(0.05)
    assert sync["compute"] == pytest.approx(0.3)  # dispatch + wait
    pipe = goodput.buckets_from_spans(1.0, spans, pipelined=True,
                                      compute_wait_s=0.1)
    assert pipe["prefetch_stall"] == pytest.approx(0.03)
    assert pipe["h2d"] == 0.0
    assert pipe["compute"] == pytest.approx(0.3)
    assert goodput.buckets_from_spans(1.0, None)["drain"] == 1.0


# ------------------------------------------------------------ work model
def test_cost_analysis_graceful_absence(monkeypatch):
    """No round program counted (the plain jit, a mesh, a backend the store
    does not serve): a duty-only block, never a raise; nor does a program
    whose only products are made again count as useful work."""
    monkeypatch.setattr(perf, "_matmul", {})
    monkeypatch.setattr(perf, "REGISTRY", MetricsRegistry())
    buckets = goodput.decompose(1.0, compute=0.5)
    blk = goodput.round_goodput(1.0, buckets, variant="v")
    assert "flops_per_s" not in blk and "mfu" not in blk
    assert "bytes_per_s" not in blk
    assert blk["duty"]["compute"] == pytest.approx(0.5)
    assert blk["variant"] == "v"
    perf.record_matmul({("fed_x", "recompute", 5e8, 4.0): 1.0})
    blk = goodput.round_goodput(1.0, buckets, peak_flops=1e9)
    assert "flops_per_s" not in blk and "mfu" not in blk
    assert blk["bytes_per_s"] == pytest.approx(4.0)


def test_round_goodput_flops_mfu_and_block_normalization(monkeypatch):
    """The useful FLOPs are the round programs' forward and gradient
    products a round, as ``perf_instrument.matmul_ops()`` gives them: a
    scanned block's count is already a round's, and what a
    rematerialised region makes again is left out."""
    monkeypatch.setattr(perf, "_matmul", {})
    monkeypatch.setattr(perf, "REGISTRY", MetricsRegistry())
    perf.record_matmul({
        ("fed_a", "forward", 2e8, 1e8): 1.0,
        ("fed_a", "gradient", 2e8, 1e8): 2.0,
        ("fed_a", "recompute", 2e8, 1e8): 1.0,
        ("outside", "forward", 1e8, 5e7): 4.0})
    buckets = goodput.decompose(0.5, compute=0.5)
    blk = goodput.round_goodput(0.5, buckets, variant="blk", n_devices=2,
                                peak_flops=1e9)
    assert blk["flops_per_s"] == pytest.approx(2e9)  # 1e9 useful / 0.5 s
    assert blk["bytes_per_s"] == pytest.approx(1.2e9)  # every run's bytes
    assert blk["mfu"] == pytest.approx(1.0)
    assert sum(blk["duty"].values()) == pytest.approx(1.0, abs=1e-3)
    # unknown device kind -> relative-only (no mfu key)
    blk2 = goodput.round_goodput(0.5, buckets, variant="blk",
                                 device_kind="who knows")
    assert "flops_per_s" in blk2 and "mfu" not in blk2


def test_device_peak_table_substring_match():
    assert goodput.device_peak_flops("TPU v5 lite") == pytest.approx(1.97e14)
    assert goodput.device_peak_flops("TPU v5e") == pytest.approx(1.97e14)
    assert goodput.device_peak_flops("TPU v5p") == pytest.approx(4.59e14)
    assert goodput.device_peak_flops("TPU v4") == pytest.approx(2.75e14)
    assert goodput.device_peak_flops("cpu") is None


def test_package_peak_equals_the_benchmarks():
    """The package holds one peak table (``utils/flops.PEAK_BF16``, read by
    goodput and by chip_smoke.py) and the benchmark holds its own, which
    the package must not import: for every device kind the benchmark knows
    the two give the same bf16 peak."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        peaks = {k: v for k, v in json.load(f).items()
                 if not k.startswith("_")}
    assert "TPU v5 lite" in peaks
    for kind, row in peaks.items():
        assert goodput.device_peak_flops(kind) == row["bf16_flops_per_s"]


# --------------------------------------------------- family registration
def test_goodput_families_preregister_at_zero():
    """Telemetry() pre-registers every new family: a clean run's export
    carries them at 0 — 'no goodput yet' must not read as missing."""
    tel = Telemetry()
    tel.close()
    snap = REGISTRY.snapshot()
    for fam in ("fed_goodput_flops_per_sec", "fed_goodput_bytes_per_sec",
                "fed_goodput_mfu", "fed_goodput_rounds_total",
                "fed_xla_variant_compiles_total",
                "fed_xla_variant_compile_seconds_total",
                "fed_xla_variant_cache_hits_total",
                "fed_xla_variant_cache_misses_total"):
        assert fam in snap, f"{fam} not pre-registered"
    duty = snap["fed_duty_cycle"]
    for b in goodput.BUCKETS:
        assert any(f"bucket={b}" in k for k in duty), f"duty {b} missing"


def test_compile_attribution_and_stats():
    """attribute_compiles scopes the per-variant families on the compiling
    thread; unattributed events land under the reserved '_other'."""
    with perf.attribute_compiles("round_unit_v1"):
        perf._on_duration("/jax/backend_compile_duration", 1.5)
        perf._on_event("/jax/compilation_cache/cache_hits")
    perf._on_duration("/jax/backend_compile_duration", 0.5)  # unattributed
    stats = perf.variant_compile_stats()
    v = stats["round_unit_v1"]
    assert v["compiles"] >= 1.0
    assert v["seconds"] >= 1.5
    assert v["cache_hits"] >= 1.0
    assert stats[perf.UNATTRIBUTED_VARIANT]["compiles"] >= 1.0
    # the context restores: a fresh event is unattributed again
    assert perf._compile_variant() == perf.UNATTRIBUTED_VARIANT


# --------------------------------------------------------- engine rounds
def test_round_records_carry_goodput_and_sum_to_wall(lr_data, lr_task):
    tel = Telemetry()
    api = FedAvgAPI(lr_data, lr_task, _cfg(), telemetry=tel)
    api.warmup()
    for r in range(3):
        api.run_round(r)
    recs = [r for r in tel.events.sink.records if r.get("kind") == "round"]
    tel.close()
    assert len(recs) == 3
    for r in recs:
        gp = r["goodput"]
        assert set(gp["buckets"]) == set(goodput.BUCKETS)
        assert sum(gp["buckets"].values()) == pytest.approx(
            gp["wall_s"], abs=1e-5)
        assert sum(gp["duty"].values()) == pytest.approx(1.0, abs=1e-2)
        assert gp["variant"].startswith("round")


def test_pipelined_records_carry_goodput(lr_data, lr_task):
    tel = Telemetry()
    api = FedAvgAPI(lr_data, lr_task, _cfg(), prefetch=2, telemetry=tel)
    api.run_pipelined(0, 4)
    recs = [r for r in tel.events.sink.records if r.get("kind") == "round"]
    tel.close()
    gps = [r.get("goodput") for r in recs]
    # the first drain has no prior inter-drain interval -> no block there;
    # every later drain carries one
    assert sum(1 for g in gps if g) >= len(recs) - 1
    for g in gps:
        if g:
            assert sum(g["buckets"].values()) == pytest.approx(
                g["wall_s"], abs=1e-5)


def test_instrumentation_off_bitwise_identical_model_bits(lr_data, lr_task):
    """Telemetry off vs on: the model bits must match EXACTLY — the
    goodput syncs ride only the telemetry path (which was about to sync
    on the same arrays anyway)."""
    plain = FedAvgAPI(lr_data, lr_task, _cfg())
    for r in range(3):
        plain.run_round(r)
    tel = Telemetry()
    instr = FedAvgAPI(lr_data, lr_task, _cfg(), telemetry=tel)
    for r in range(3):
        instr.run_round(r)
    tel.close()
    for a, b in zip(_leaves(plain), _leaves(instr)):
        assert a.tobytes() == b.tobytes()
    # pipelined twin: same contract
    plain_p = FedAvgAPI(lr_data, lr_task, _cfg(), prefetch=2)
    plain_p.run_pipelined(0, 3)
    tel2 = Telemetry()
    instr_p = FedAvgAPI(lr_data, lr_task, _cfg(), prefetch=2,
                        telemetry=tel2)
    instr_p.run_pipelined(0, 3)
    tel2.close()
    for a, b in zip(_leaves(plain_p), _leaves(instr_p)):
        assert a.tobytes() == b.tobytes()


@pytest.mark.slow
def test_instrumentation_off_identical_wire_bytes(lr_data, lr_task):
    """Loopback sim with vs without telemetry: identical model bits AND
    identical uplink/downlink wire bytes — observability must not change
    what crosses the wire."""
    from fedml_tpu.comm.message import pack_pytree
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs.comm_instrument import comm_counters

    def _run(telemetry):
        before = comm_counters()
        agg = run_simulated(lr_data, lr_task, _cfg(rounds=2),
                            job_id="gp-wire", telemetry=telemetry)
        after = comm_counters()
        delta = {k: after[k] - before[k]
                 for k in ("bytes_uplink", "bytes_downlink")}
        return agg, delta

    agg_off, bytes_off = _run(None)
    tel = Telemetry()
    agg_on, bytes_on = _run(tel)
    tel.close()
    assert bytes_off == bytes_on
    for a, b in zip(pack_pytree(agg_off.net), pack_pytree(agg_on.net)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.slow
def test_chaos_straggle_moves_exactly_wire_wait(lr_data, lr_task):
    """A seeded straggle fault on the loopback wire lands in wire_wait —
    and ONLY wire_wait moves materially (the forensic attribution the
    run-store diff is built on)."""
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed.fedavg import run_simulated

    delay = 0.4

    def _buckets(chaos_plan):
        tel = Telemetry()
        run_simulated(lr_data, lr_task, _cfg(rounds=2), job_id="gp-chaos",
                      telemetry=tel, chaos_plan=chaos_plan)
        recs = [r for r in tel.events.sink.records
                if r.get("kind") == "round" and r.get("goodput")]
        tel.close()
        assert recs, "server rounds carry no goodput block"
        out = {}
        for b in goodput.BUCKETS:
            vals = [r["goodput"]["buckets"][b] for r in recs]
            out[b] = sum(vals) / len(vals)
        return out

    base = _buckets(None)
    plan = FaultPlan.from_json(
        {"seed": 7, "rules": [{"fault": "straggle", "src": [2],
                               "delay_s": delay}]})
    straggled = _buckets(plan)
    deltas = {b: straggled[b] - base[b] for b in goodput.BUCKETS}
    assert deltas["wire_wait"] > 0.5 * delay, deltas
    moved = max(deltas, key=lambda k: abs(deltas[k]))
    assert moved == "wire_wait", deltas


# -------------------------------------------------------------- runstore
def _round_rec(i, ts, stall, compute=0.02, drain=0.001):
    wall = compute + stall + drain
    buckets = {b: 0.0 for b in goodput.BUCKETS}
    buckets.update(compute=compute, prefetch_stall=stall, drain=drain)
    return {"kind": "round", "round": i, "ts": ts,
            "comm": {"bytes_uplink": 100 * (i + 1),
                     "bytes_downlink": 200 * (i + 1)},
            "privacy": {"eps": 0.1 * (i + 1)},
            "goodput": {"wall_s": wall, "buckets": buckets,
                        "duty": {b: v / wall for b, v in buckets.items()},
                        "flops_per_s": 1e9}}


def _write_log(path, stall):
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "run", "run": os.path.basename(path),
                            "ts": 0.0}) + "\n")
        for i in range(5):
            f.write(json.dumps(_round_rec(i, 10.0 + 0.1 * i, stall)) + "\n")


def test_runstore_ingest_diff_trend_and_gate(tmp_path):
    from scripts import runstore

    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    _write_log(a, stall=0.030)
    _write_log(b, stall=0.002)
    # a historical BENCH blob without a provenance header must index fine
    blob_path = str(tmp_path / "BENCH_old.json")
    with open(blob_path, "w") as f:
        json.dump({"metric": "fedavg_rounds_per_sec", "value": 1.5,
                   "rounds": 20}, f)
    index = str(tmp_path / "index.jsonl")
    rc = runstore.main(["--index", index, "ingest", a, b, blob_path])
    assert rc == 0
    entries = runstore._load_index(index)
    assert len(entries) == 3
    assert entries[0]["summary"]["rounds"] == 5
    assert entries[0]["summary"]["bucket_s"]["prefetch_stall"] == \
        pytest.approx(0.030)
    assert entries[0]["summary"]["eps"] == pytest.approx(0.5)
    assert entries[0]["summary"]["rounds_per_sec"] == pytest.approx(10.0)
    assert entries[2]["kind"] == "bench"
    assert entries[2]["provenance"] is None  # headerless: tolerated
    assert entries[2]["summary"]["value"] == 1.5
    # idempotent: re-ingest dedupes on sha256
    rc = runstore.main(["--index", index, "ingest", a])
    assert rc == 0
    assert len(runstore._load_index(index)) == 3
    # diff names the moved bucket
    ea, eb = runstore._resolve(entries, "a.jsonl"), \
        runstore._resolve(entries, "b.jsonl")
    lines, moved = runstore.diff_entries(ea, eb)
    assert moved == "prefetch_stall"
    assert any("moved bucket: prefetch_stall" in ln for ln in lines)
    assert runstore.main(["--index", index, "diff", "a.jsonl",
                          "b.jsonl"]) == 0
    assert runstore.main(["--index", index, "trend"]) == 0
    assert runstore.main(["--index", index, "list"]) == 0
    # the bench_gate hook over the flattened summary
    flat = runstore.flatten_summary(eb)
    assert flat["bucket_prefetch_stall_s"] == pytest.approx(0.002)
    assert flat["duty_total"] == pytest.approx(1.0, abs=0.01)
    gate = str(tmp_path / "gate.json")
    with open(gate, "w") as f:
        json.dump({"metrics": {
            "rounds": {"baseline": 5, "exact": True},
            "duty_total": {"min_abs": 0.8, "max_abs": 1.2,
                           "required": True},
            "duty_prefetch_stall": {"max_abs": 0.5}}}, f)
    assert runstore.main(["--index", index, "gate", "b.jsonl",
                          "--gate", gate]) == 0
    with open(gate, "w") as f:
        json.dump({"metrics": {
            "duty_prefetch_stall": {"max_abs": 1e-9,
                                    "required": True}}}, f)
    assert runstore.main(["--index", index, "gate", "b.jsonl",
                          "--gate", gate]) == 1


def test_runstore_pre_goodput_logs_degrade(tmp_path):
    """Logs that predate the goodput block index and diff without it."""
    from scripts import runstore

    old = str(tmp_path / "old.jsonl")
    with open(old, "w") as f:
        for i in range(3):
            f.write(json.dumps({"kind": "round", "round": i,
                                "ts": float(i)}) + "\n")
    index = str(tmp_path / "index.jsonl")
    assert runstore.main(["--index", index, "ingest", old]) == 0
    entries = runstore._load_index(index)
    assert entries[0]["summary"]["rounds"] == 3
    assert "bucket_s" not in entries[0]["summary"]
    lines, moved = runstore.diff_entries(entries[0], entries[0])
    assert moved is None
    assert any("no goodput buckets" in ln for ln in lines)
    # gating a pre-goodput entry fails only on required metrics
    flat = runstore.flatten_summary(entries[0])
    assert "duty_total" not in flat


def test_committed_ci_gate_file_parses():
    """The committed gate file must stay loadable and carry the
    structural checks the ci.sh leg depends on."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "ci_goodput_gate.json")
    with open(path) as f:
        gate = json.load(f)
    metrics = gate["metrics"]
    assert metrics["duty_total"]["required"]
    assert "duty_prefetch_stall" in metrics
    assert metrics["rounds"]["exact"]


# ------------------------------------------------------------ provenance
def test_provenance_stamp_never_overwrites(tmp_path):
    prov = provenance(date="2026-08-07", dataset_source="synthetic")
    assert prov["date"] == "2026-08-07"
    assert prov["dataset_source"] == "synthetic"
    assert "git_sha" in prov and "jax" in prov and "device_kind" in prov
    blob = {"metric": "x", "value": 1.0}
    stamp(blob, date="2026-08-07")
    assert blob["provenance"]["date"] == "2026-08-07"
    # a second stamp NEVER overwrites the measuring process's header
    stamp(blob, date="1999-01-01")
    assert blob["provenance"]["date"] == "2026-08-07"


# ------------------------------------------------------- report / fedtop
def test_report_goodput_columns_hide_on_old_logs():
    from scripts.report import render_compiles, render_table

    old = [{"kind": "round", "round": 0, "clients": [1], "metrics": {},
            "spans": {"round": 0.1}}]
    out = render_table(old)
    assert "duty_cmp" not in out and "gflops" not in out and "mfu" not in out
    new = [dict(old[0], goodput={
        "wall_s": 0.1, "flops_per_s": 2e9, "mfu": 0.01,
        "buckets": {b: 0.0 for b in goodput.BUCKETS},
        "duty": {"compute": 0.9, "prefetch_stall": 0.05}})]
    out = render_table(new)
    assert "duty_cmp" in out and "gflops" in out and "mfu" in out
    assert "0.9" in out and "2" in out
    # --compiles: old logs degrade to a notice, new logs render variants
    assert "predates" in render_compiles(old)
    rendered = render_compiles([{
        "kind": "compiles", "seconds": 1.2, "fresh": 1, "cache_hits": 0,
        "cache_misses": 1, "instrumented": True,
        "variants": {"round_b8": {"seconds": 0.7}},
        "attribution": {"round_b8": {"seconds": 0.6, "compiles": 1.0}}}])
    assert "round_b8" in rendered and "0.7" in rendered


def test_fedtop_duty_gflops_columns_hide_on_old_digests():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fedtop", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "fedtop.py"))
    fedtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fedtop)
    snap = {"run": "r", "status": "active", "ranks": {
        "1": {"status": "active", "round": 2, "duty": 0.875,
              "gflops": 12.5},
        "2": {"status": "active", "round": 2}}}
    out = fedtop.render(snap)
    assert "duty%" in out and "gflops" in out
    assert "87.5" in out and "12.5" in out
    row2 = [ln for ln in out.splitlines() if ln.strip().startswith("2")][0]
    assert "-" in row2  # pre-PR digests render '-'


# ------------------------------------------- fused ingest attribution (PR-21)
def test_fused_ingest_seconds_move_wire_wait_into_agg_flush():
    """The server's goodput block moves the per-arrival fused ingest-jit
    seconds out of wire_wait (where the wall-clock window places them —
    the jits run while the server waits on stragglers) into agg_flush
    (what the seconds actually are: aggregation work). A stacked manager
    (no ingest accumulator) is byte-identical to the pre-PR block."""
    import types

    from fedml_tpu.distributed.fedavg.server_manager import (
        FedAvgServerManager,
    )

    spans = {"aggregate": 0.1}
    fused = types.SimpleNamespace(_gp_fused_ingest_s=0.3)
    g = FedAvgServerManager._goodput_extra(
        fused, spans, wire_wait_s=0.5, wall_s=1.0)["goodput"]
    assert g["buckets"]["wire_wait"] == pytest.approx(0.2)
    assert g["buckets"]["agg_flush"] == pytest.approx(0.4)
    stacked = types.SimpleNamespace()
    g2 = FedAvgServerManager._goodput_extra(
        stacked, spans, wire_wait_s=0.5, wall_s=1.0)["goodput"]
    assert g2["buckets"]["wire_wait"] == pytest.approx(0.5)
    assert g2["buckets"]["agg_flush"] == pytest.approx(0.1)
    # attribution never goes negative when the window under-measures
    clipped = types.SimpleNamespace(_gp_fused_ingest_s=0.9)
    g3 = FedAvgServerManager._goodput_extra(
        clipped, spans, wire_wait_s=0.5, wall_s=2.0)["goodput"]
    assert g3["buckets"]["wire_wait"] == 0.0
    assert g3["buckets"]["agg_flush"] == pytest.approx(1.0)


def test_runstore_diff_names_agg_flush_for_fused_attribution(tmp_path):
    """The forensic pin for the attribution fix: two run logs identical
    except that the fused ingest seconds sit in wire_wait (pre-fix) vs
    agg_flush (post-fix) — the run-store diff names agg_flush as THE
    moved bucket, which is how a fused A/B reads in the index."""
    from scripts import runstore

    def rec(i, wire_wait, agg_flush):
        wall = 0.02 + wire_wait + agg_flush
        buckets = {b: 0.0 for b in goodput.BUCKETS}
        buckets.update(compute=0.02, wire_wait=wire_wait,
                       agg_flush=agg_flush)
        return {"kind": "round", "round": i, "ts": 10.0 + 0.1 * i,
                "goodput": {"wall_s": wall, "buckets": buckets,
                            "duty": {b: v / wall
                                     for b, v in buckets.items()}}}

    def write(path, wire_wait, agg_flush):
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "run",
                                "run": os.path.basename(path),
                                "ts": 0.0}) + "\n")
            for i in range(4):
                f.write(json.dumps(rec(i, wire_wait, agg_flush)) + "\n")

    pre, post = str(tmp_path / "pre.jsonl"), str(tmp_path / "post.jsonl")
    # post-fix the ingest seconds land in agg_flush AND the flush itself
    # got faster, so agg_flush is the strictly-largest mover
    write(pre, wire_wait=0.050, agg_flush=0.004)   # ingest hidden in wait
    write(post, wire_wait=0.012, agg_flush=0.048)  # ingest attributed
    index = str(tmp_path / "index.jsonl")
    assert runstore.main(["--index", index, "ingest", pre, post]) == 0
    entries = runstore._load_index(index)
    ea = runstore._resolve(entries, "pre.jsonl")
    eb = runstore._resolve(entries, "post.jsonl")
    lines, moved = runstore.diff_entries(ea, eb)
    assert moved == "agg_flush", lines
    assert any("moved bucket: agg_flush" in ln for ln in lines)
