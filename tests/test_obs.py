"""Telemetry subsystem (fedml_tpu/obs): metrics registry, event log,
comm instrumentation, engine integration, and the run reporter.

The load-bearing oracle is the loopback integration test: a cross-process
FedAvg run with telemetry enabled writes a JSONL event log whose per-round
records carry span timings, sampled client ids, the aggregate update norm,
and NONZERO comm byte/message counters — and scripts/report.py renders it
into a table plus a BENCH-compatible JSON blob.
"""

import importlib.util
import json
import math
import os
import threading

import numpy as np
import pytest

from fedml_tpu.obs.events import EventLog, JsonlSink, MemorySink, read_jsonl
from fedml_tpu.obs.metrics import Histogram, MetricsRegistry
from fedml_tpu.obs.telemetry import Telemetry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ metrics
def test_counter_gauge_and_label_families():
    reg = MetricsRegistry()
    reg.counter("msgs", backend="loopback").inc()
    reg.counter("msgs", backend="loopback").inc(2)
    reg.counter("msgs", backend="grpc").inc(5)
    reg.gauge("temp").set(3.5)
    snap = reg.snapshot()
    assert snap["msgs"]["backend=loopback"] == 3.0
    assert snap["msgs"]["backend=grpc"] == 5.0
    assert snap["temp"][""] == 3.5
    assert reg.total("msgs") == 8.0
    assert reg.total("nonexistent") == 0.0
    with pytest.raises(ValueError):
        reg.counter("msgs").inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("msgs")  # kind collision must be loud


def test_histogram_streaming_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    assert math.isnan(h.quantile(0.5))
    for v in range(1, 1001):
        h.observe(v / 1000.0)  # 1ms .. 1s uniform
    s = h.summary()
    assert s["count"] == 1000
    assert s["min"] == 0.001 and s["max"] == 1.0
    np.testing.assert_allclose(s["sum"], 500.5, rtol=1e-6)
    # geometric buckets (10/decade): quantiles within ~±13% of exact
    np.testing.assert_allclose(s["p50"], 0.5, rtol=0.2)
    np.testing.assert_allclose(s["p95"], 0.95, rtol=0.2)
    np.testing.assert_allclose(s["p99"], 0.99, rtol=0.2)
    # out-of-span values clamp into edge buckets but stay exact in min/max
    h.observe(1e-9)
    h.observe(1e9)
    assert h.summary()["min"] == 1e-9 and h.summary()["max"] == 1e9


def test_histogram_thread_safety_count_exact():
    h = Histogram(threading.Lock())

    def hammer():
        for _ in range(1000):
            h.observe(0.01)

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == 4000 and sum(h._buckets) == 4000


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("comm_bytes_sent_total", backend="loopback",
                codec="f16").inc(1024)
    reg.histogram("lat", backend="loopback").observe(0.25)
    text = reg.to_prometheus()
    assert "# TYPE comm_bytes_sent_total counter" in text
    assert 'comm_bytes_sent_total{backend="loopback",codec="f16"} 1024' in text
    assert 'lat_count{backend="loopback"} 1' in text
    assert 'quantile="0.5"' in text


# ------------------------------------------------------------------- events
def test_event_log_memory_sink():
    log = EventLog(MemorySink(), run_id="r1", clock=lambda: 123.0)
    log.emit("run", config={"lr": 0.1})
    log.emit("round", round=0, metrics={"loss": 1.0})
    recs = log.sink.records
    assert [r["kind"] for r in recs] == ["run", "round"]
    assert recs[0] == {"ts": 123.0, "kind": "run", "run": "r1",
                      "config": {"lr": 0.1}}
    assert json.loads(json.dumps(recs[1]))  # every record is jsonable


def test_jsonl_sink_rotation_and_readback(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path, max_bytes=300, backups=2)
    log = EventLog(sink, run_id="rot")
    for i in range(20):
        log.emit("round", round=i)
    log.close()
    assert os.path.exists(path + ".1")  # rotation happened
    recs = read_jsonl(path)
    rounds = [r["round"] for r in recs if r["kind"] == "round"]
    # oldest segments beyond the backup budget are dropped; what's retained
    # comes back in emission order and always includes the newest record
    assert rounds == sorted(rounds) and rounds[-1] == 19
    assert all(os.path.getsize(p) <= 300 + 120
               for p in (path, path + ".1") if os.path.exists(p))


def test_read_jsonl_skips_corrupt_lines(tmp_path):
    p = tmp_path / "ev.jsonl"
    p.write_text('{"kind": "round", "round": 0}\n{oops\n'
                 '{"kind": "round", "round": 1}\n')
    recs = read_jsonl(str(p), kinds=("round",))
    assert [r["round"] for r in recs] == [0, 1]


# ------------------------------------------------- engine integration (SPMD)
@pytest.fixture(scope="module")
def lr_setup():
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.linear import LogisticRegression

    data = synthetic_images(num_clients=8, image_shape=(8, 8, 1),
                            num_classes=4, samples_per_client=24,
                            test_samples=96, seed=3)
    task = classification_task(LogisticRegression(num_classes=4))
    return data, task


def test_standalone_round_stats_and_nil_when_off(lr_setup):
    """Telemetry on: the jitted round program returns update-norm/drift
    stats IN the metrics dict (no second program, no extra sync). Telemetry
    off: the metrics keys are exactly the seed's — the round program gained
    nothing."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig

    data, task = lr_setup
    cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                       client_num_per_round=4, batch_size=8, lr=0.1,
                       frequency_of_the_test=1, seed=0)
    off = FedAvgAPI(data, task, cfg)
    m_off = off.run_round(0)
    assert set(m_off.keys()) == {"loss_sum", "correct", "count"}

    tel = Telemetry(registry=MetricsRegistry())  # memory sink
    on = FedAvgAPI(data, task, cfg, telemetry=tel)
    on.train()
    records = tel.events.sink.records
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "run" and "round" in kinds and "eval" in kinds
    rounds = [r for r in records if r["kind"] == "round"]
    assert [r["round"] for r in rounds] == [0, 1]
    for r in rounds:
        assert len(r["clients"]) == 4
        assert r["spans"]["round"] > 0 and r["spans"]["pack"] > 0
        assert r["metrics"]["update_norm"] > 0
        assert r["metrics"]["client_drift_mean"] > 0
        assert (r["metrics"]["client_drift_max"]
                >= r["metrics"]["client_drift_mean"])
        assert r["comm"]["bytes_sent"] == 0  # standalone: no wire traffic
    # telemetry did not change the training itself
    from fedml_tpu.comm.message import pack_pytree

    ref = FedAvgAPI(data, task, cfg)
    ref.train()
    for a, b in zip(pack_pytree(ref.net), pack_pytree(on.net)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_direct_run_round_spans_are_per_call_deltas(lr_setup):
    """bench-style drivers call run_round() directly without train()'s
    next_round(), so the tracer's round dict accumulates — each emitted
    record must carry THIS call's span delta, and the deltas must sum to
    the tracer's running total (not each record repeating it)."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig

    data, task = lr_setup
    cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                       client_num_per_round=4, batch_size=8, lr=0.1, seed=0)
    tel = Telemetry(registry=MetricsRegistry())
    api = FedAvgAPI(data, task, cfg, telemetry=tel)
    for r in range(3):
        api.run_round(r)  # no next_round between calls
    recs = tel.events.sink.records
    spans = [r["spans"]["round"] for r in recs]
    assert all(s > 0 for s in spans)
    total = api.tracer.rounds[-1]["round"]
    np.testing.assert_allclose(sum(spans), total, rtol=1e-6)
    # cumulative emission would make each record >= the running total
    assert spans[1] < total and spans[2] < total


def test_block_engine_emits_per_round_records(lr_setup):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig

    data, task = lr_setup
    cfg = FedAvgConfig(comm_round=4, client_num_in_total=8,
                       client_num_per_round=4, batch_size=8, lr=0.1, seed=0)
    tel = Telemetry(registry=MetricsRegistry())
    api = FedAvgAPI(data, task, cfg, device_data=True, telemetry=tel)
    api.run_rounds(0, 4)
    recs = tel.events.sink.records
    assert [r["kind"] for r in recs] == ["block"] + ["round"] * 4
    assert recs[0]["spans"]["round"] > 0
    for i, r in enumerate(recs[1:]):
        assert r["round"] == i and r["block"] is True
        assert r["metrics"]["update_norm"] > 0
        assert len(r["clients"]) == 4


# ------------------------------------------ loopback integration (the oracle)
def test_loopback_run_emits_full_round_schema(lr_setup, tmp_path):
    """Acceptance oracle: a loopback FedAvg run with telemetry enabled
    writes a JSONL event log whose per-round records include span timings,
    sampled client ids, aggregate update norm, and nonzero comm
    byte/message counters."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=8,
                       lr=0.1, frequency_of_the_test=1, seed=0)
    tel = Telemetry(log_dir=str(tmp_path))
    agg = run_simulated(data, task, cfg, backend="LOOPBACK",
                        job_id="t-obs", telemetry=tel)
    tel.close()
    assert agg.history and agg.history[-1]["round"] == cfg.comm_round - 1

    recs = read_jsonl(str(tmp_path / "events.jsonl"))
    header = [r for r in recs if r["kind"] == "run"]
    assert header and header[0]["engine"] == "distributed"
    assert header[0]["config"]["comm_round"] == 3
    rounds = [r for r in recs if r["kind"] == "round"]
    assert [r["round"] for r in rounds] == [0, 1, 2]
    for r in rounds:
        assert len(r["clients"]) == 4
        assert r["spans"]["aggregate"] > 0 and "eval" in r["spans"]
        assert r["metrics"]["update_norm"] > 0
        assert r["metrics"]["num_samples"] > 0
        assert r["comm"]["messages_sent"] > 0      # the wire was exercised
        assert r["comm"]["bytes_sent"] > 1000      # model frames, not acks
        assert r["comm"]["messages_received"] > 0
        assert r["eval"]["test_acc"] >= 0          # eval folded in (freq=1)
    # the registry's prometheus dump landed next to the event log
    prom = (tmp_path / "metrics.prom").read_text()
    assert "comm_bytes_sent_total" in prom
    assert 'backend="loopback"' in prom
    assert "comm_dispatch_latency_seconds_count" in prom


# ----------------------------------------------------------------- reporter
def _load_report():
    spec = importlib.util.spec_from_file_location(
        "report", os.path.join(REPO_ROOT, "scripts", "report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_report_roundtrip_on_recorded_run(lr_setup, tmp_path, capsys):
    """scripts/report.py renders a recorded run and emits a
    BENCH-compatible JSON blob (the round-trip: run -> events.jsonl ->
    report -> summary)."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig

    data, task = lr_setup
    cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                       client_num_per_round=4, batch_size=8, lr=0.1,
                       frequency_of_the_test=1, seed=0)
    tel = Telemetry(log_dir=str(tmp_path), registry=MetricsRegistry())
    FedAvgAPI(data, task, cfg, telemetry=tel).train()
    tel.close()

    report = _load_report()
    events = str(tmp_path / "events.jsonl")
    bench_out = str(tmp_path / "bench.json")
    csv_out = str(tmp_path / "rounds.csv")
    rc = report.main([events, "--bench-json", bench_out, "--csv", csv_out])
    assert rc == 0
    table = capsys.readouterr().out
    assert "round" in table and "upd_norm" in table and "test_acc" in table

    with open(bench_out) as f:
        blob = json.load(f)
    assert blob["unit"] == "rounds/sec" and blob["rounds"] == 3
    assert blob["value"] > 0 and blob["basis"] == "span"
    assert blob["final_test_acc"] >= 0

    with open(csv_out) as f:
        lines = f.read().strip().splitlines()
    assert len(lines) == 1 + 3  # header + one row per round
    assert "metrics.update_norm" in lines[0]

    # stdout mode: the blob is the last stdout line, parseable
    rc = report.main([events, "--bench-json", "-"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["rounds"] == 3

    # empty/missing input fails loudly, not with a stack trace
    assert report.main([str(tmp_path / "nope.jsonl")]) == 1


# ------------------------------------------------------------- wire symmetry
def test_json_codec_symmetric_for_all_array_keys():
    """ADVICE r5 item 1: with --compression json, NON-fedavg protocols'
    array params (split_nn acts, sparse idx/val...) must decode back to
    ndarrays with the sender's dtype — not nested python lists."""
    from fedml_tpu.comm.message import Message

    m = Message("c2s_acts", 1, 0)
    m.add_params("acts", np.arange(12, dtype=np.float32).reshape(3, 4))
    m.add_params("sparse_idx", [np.array([0, 5, 9], np.int64),
                                np.array([2], np.int64)])
    m.add_params("num_samples", 7)
    frame = m.to_bytes("json")
    doc = json.loads(frame)  # still a plain JSON object (reference interop)
    assert isinstance(doc["acts"][0], list)

    back = Message.from_bytes(frame)
    acts = back.get("acts")
    assert isinstance(acts, np.ndarray) and acts.dtype == np.float32
    np.testing.assert_array_equal(acts, m.get("acts"))
    idx = back.get("sparse_idx")
    assert all(isinstance(a, np.ndarray) and a.dtype == np.int64
               for a in idx)
    np.testing.assert_array_equal(idx[0], [0, 5, 9])
    assert back.get("num_samples") == 7
