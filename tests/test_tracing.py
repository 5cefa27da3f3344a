"""Tracing subsystem: span stats, engine integration, and the cross-rank
distributed tracer (obs/tracing.py) — stitched per-round timelines,
NTP-style clock-offset recovery, critical-path/straggler attribution,
chaos cross-referencing, and the Chrome trace-event export (golden file,
deterministic ids under an injected clock)."""

import json
import os
import time

import numpy as np
import pytest

from fedml_tpu.obs.clock import ClockSync, estimate
from fedml_tpu.obs.metrics import REGISTRY
from fedml_tpu.obs.tracing import (TRACE_KEY, ClientSpanBuffer,
                                   DistributedTracer, RoundTracer)

_DATA_DIR = os.path.join(os.path.dirname(__file__), "data")


# ----------------------------------------------------------- RoundTracer
def test_round_tracer_spans_and_summary():
    tr = RoundTracer()
    for _ in range(3):
        with tr.span("pack"):
            time.sleep(0.002)
        with tr.span("round"):
            time.sleep(0.004)
        tr.next_round()
    s = tr.summary()
    assert s["pack"]["count"] == 3 and s["round"]["count"] == 3
    assert s["round"]["mean"] >= s["pack"]["mean"]
    assert s["pack"]["total"] >= 0.006


def test_span_accumulates_within_round():
    tr = RoundTracer()
    with tr.span("x"):
        pass
    with tr.span("x"):
        pass
    assert tr.summary()["x"]["count"] == 1  # same round -> one accumulated entry


def test_round_tracer_feeds_registry_histogram():
    """Satellite: RoundTracer spans land in the process registry's
    fed_span_seconds histogram, so tracer.summary() and the Prometheus
    export read ONE timing path (the histogram counts observations)."""
    h = REGISTRY.histogram("fed_span_seconds", span="t_hist_unit")
    before_n, before_sum = h.count, h.total
    tr = RoundTracer()
    with tr.span("t_hist_unit"):
        time.sleep(0.002)
    with tr.span("t_hist_unit"):
        pass
    assert h.count == before_n + 2
    total = tr.summary()["t_hist_unit"]["total"]
    assert abs((h.total - before_sum) - total) < 5e-3
    assert "fed_span_seconds" in REGISTRY.to_prometheus()


def test_span_annotation_noop_outside_trace():
    tr = RoundTracer()
    with tr.span("region", round=3):
        pass  # the fed:region annotation must not raise with no profiler
    assert tr.totals()["region"] >= 0.0


def test_engine_populates_tracer():
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.linear import LogisticRegression

    data = synthetic_images(num_clients=4, image_shape=(8, 8, 1), num_classes=3,
                            samples_per_client=12, test_samples=30, seed=0)
    api = FedAvgAPI(data, classification_task(LogisticRegression(num_classes=3)),
                    FedAvgConfig(comm_round=2, client_num_in_total=4,
                                 client_num_per_round=2, batch_size=6,
                                 frequency_of_the_test=1))
    api.train()
    s = api.tracer.summary()
    assert s["pack"]["count"] == 2 and s["round"]["count"] == 2
    assert "eval" in s


# ------------------------------------------------------------ clock sync
def test_clock_offset_recovers_skew():
    """Synthetic skewed clocks: with symmetric wire legs the NTP estimator
    recovers the offset exactly; an asymmetry of `a` biases it by a/2."""
    true_off, wire = 3.25, 0.010
    t1 = 100.0
    t2 = t1 + wire + true_off          # client clock = server + 3.25
    t3 = t2 + 0.5                      # client compute
    t4 = t3 - true_off + wire          # back on the server clock
    off, rtt = estimate(t1, t2, t3, t4)
    assert abs(off - true_off) < 1e-9
    assert abs(rtt - 2 * wire) < 1e-9

    cs = ClockSync()
    assert cs.offset(1) == 0.0  # unseen rank: rebase is the identity
    got = cs.update(1, t1, t2, t3, t4)
    assert abs(got - true_off) < 1e-9

    # asymmetric legs (0.5 ms down, 20 ms up): bias bounded by asym/2
    t2a = t1 + 0.0005 + true_off
    t3a = t2a + 0.5
    t4a = t3a - true_off + 0.020
    off_a, _ = estimate(t1, t2a, t3a, t4a)
    assert abs(off_a - true_off) <= 0.020 / 2 + 1e-9


def test_clock_sync_min_rtt_filter():
    """The clock filter keeps the minimum-RTT sample (least queueing =
    least asymmetry), so one congested exchange cannot poison the rank's
    estimate."""
    cs = ClockSync()
    cs.update(3, 0.0, 1.001, 1.101, 0.102)      # clean: off=1.0, rtt=2ms
    noisy = cs.update(3, 10.0, 11.3, 11.4, 10.5)  # congested uplink
    assert abs(noisy - 1.0) < 1e-6  # min-RTT sample still wins
    assert abs(cs.snapshot()[3]["offset_s"] - 1.0) < 1e-6


# ----------------------------------------------------- golden trace export
def _fixed_clock(start=1000.0, step=0.125):
    t = {"now": start}

    def clock():
        t["now"] += step
        return t["now"]

    return clock


def _build_golden_trace():
    """The deterministic reference trace: server broadcasts to ranks 1-2,
    both report, rank 2 (fewer spans -> later T3 relative to fake-clock
    ticks) straggles. Ids are sha256 of (run, round, rank, counter) and
    the clock is injected, so the export is byte-stable."""
    from fedml_tpu.obs import comm_instrument as _ci

    # an earlier test's loopback sim may have run a dispatch loop on THIS
    # thread, leaving a thread-local last-dispatch latency behind — which
    # ClientSpanBuffer.span would dutifully attach as a queue_wait attr and
    # break the byte-stable golden comparison (order-dependent flake)
    _ci._tls.last_dispatch_s = None
    clock = _fixed_clock()
    tr = DistributedTracer("golden-run", clock=clock)
    tr.begin_round(0)
    c1, c2 = tr.broadcast_ctx(1), tr.broadcast_ctx(2)
    tr.end_broadcast()
    b1 = ClientSpanBuffer(1, clock=clock)
    b1.on_broadcast(c1)
    with b1.span("unpack"):
        pass
    with b1.span("local_fit"):
        pass
    with b1.span("pack"):
        pass
    tr.on_upload(1, b1.upload_blob())
    b2 = ClientSpanBuffer(2, clock=clock)
    b2.on_broadcast(c2)
    with b2.span("local_fit"):
        pass
    tr.on_upload(2, b2.upload_blob())
    tr.record_span("aggregate", clock(), clock())
    return tr, tr.finish_round()


def test_chrome_trace_export_golden():
    from fedml_tpu.obs.trace_export import (to_chrome_trace,
                                            validate_chrome_trace,
                                            validate_spans)

    tr, cp = _build_golden_trace()
    assert validate_spans(tr.spans()) == []
    doc = to_chrome_trace(tr.spans())
    assert validate_chrome_trace(doc) == []
    with open(os.path.join(_DATA_DIR, "golden_trace.json")) as f:
        golden = json.load(f)
    assert doc == golden  # byte-stable: no Date.now-style nondeterminism
    # the critical path of the synthetic round is itself deterministic
    assert cp["straggler"] == 2
    assert cp["slack_s"] == {1: 0.625, 2: 0.0}
    assert abs(cp["phases"]["aggregate"] - 0.125) < 1e-9


def test_export_validators_catch_damage():
    from fedml_tpu.obs.trace_export import (to_chrome_trace,
                                            validate_chrome_trace,
                                            validate_spans)

    tr, _ = _build_golden_trace()
    spans = tr.spans()
    bad = [dict(s) for s in spans]
    bad[0]["parent"] = "feedfacedeadbeef"  # dangling
    assert any("dangling" in e for e in validate_spans(bad))
    bad2 = [dict(s) for s in spans]
    bad2[1]["t1"] = bad2[1]["t0"] - 1.0
    assert any("ends before" in e for e in validate_spans(bad2))
    doc = to_chrome_trace(spans)
    doc["traceEvents"][0] = {"ph": "?"}
    assert validate_chrome_trace(doc)


# ----------------------------------------------- loopback stitch (3 ranks)
@pytest.fixture(scope="module")
def sim_setup():
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.linear import LogisticRegression

    data = synthetic_images(num_clients=4, image_shape=(6, 6, 1),
                            num_classes=3, samples_per_client=12,
                            test_samples=24, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    cfg = FedAvgConfig(comm_round=2, client_num_in_total=4,
                       client_num_per_round=2, batch_size=6,
                       frequency_of_the_test=1)
    return data, task, cfg


def test_loopback_3rank_stitch(sim_setup):
    """3 ranks over loopback: one stitched timeline per round — client
    spans parented under the server's broadcast span, wire spans on both
    ends, and a critical-path record on every round."""
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs import Telemetry
    from fedml_tpu.obs.trace_export import validate_spans

    tel = Telemetry(trace=True)
    run_simulated(*sim_setup, job_id="t-stitch", telemetry=tel)
    rounds = [r for r in tel.events.sink.records if r["kind"] == "round"]
    assert [r["round"] for r in rounds] == [0, 1]
    for r in rounds:
        cp = r["critical_path"]
        assert cp["straggler"] in (1, 2)
        assert cp["slack_s"][cp["straggler"]] == 0.0
        assert {"downlink", "unpack", "local_fit", "pack", "uplink",
                "aggregate", "eval"} <= set(cp["phases"])
        assert set(cp["clock_offset_s"]) == {1, 2}

    spans = tel.tracer.spans()
    assert validate_spans(spans) == []
    assert {s["rank"] for s in spans} == {0, 1, 2}
    by_sid = {s["sid"]: s for s in spans}
    roots = [s for s in spans if s["name"] == "client_round"]
    assert len(roots) == 4  # 2 clients x 2 rounds
    for root in roots:
        assert by_sid[root["parent"]]["name"] == "broadcast"
    for kid in (s for s in spans if s["name"] in ("unpack", "local_fit",
                                                  "pack")):
        parent = by_sid[kid["parent"]]
        assert parent["name"] == "client_round"
        assert parent["rank"] == kid["rank"]
        assert parent["t0"] <= kid["t0"] and kid["t1"] <= parent["t1"] + 1e-6
    for up in (s for s in spans if s["name"] == "uplink"):
        assert by_sid[up["parent"]]["name"] == "client_round"
    # liveness gauges fed by the run's frames (satellite: heartbeat)
    snap = REGISTRY.snapshot()["fed_last_heartbeat_age_seconds"]
    assert {"rank=0", "rank=1", "rank=2"} <= set(snap)
    tel.close()


def test_chaos_straggle_owns_critical_path(sim_setup):
    """Acceptance: a planned 200 ms straggle on rank 2 must surface as
    that rank owning the round's critical path, with the injected delay
    cross-referenced from the chaos ledger and the uplink span labeled."""
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs import Telemetry

    plan = FaultPlan.from_json({"seed": 7, "rules": [
        {"fault": "straggle", "direction": "send", "src": [2], "dst": [0],
         "rounds": [1, 2], "delay_s": 0.2}]})
    tel = Telemetry(trace=True)
    run_simulated(*sim_setup, job_id="t-chaos-trace", telemetry=tel,
                  chaos_plan=plan)
    r1 = [r for r in tel.events.sink.records
          if r["kind"] == "round" and r["round"] == 1][0]
    cp = r1["critical_path"]
    assert cp["straggler"] == 2
    assert abs(cp["chaos_delay_s"][2] - 0.2) < 1e-9
    assert cp["phases"]["uplink"] >= 0.2  # the sleep sits on the wire span
    assert cp["slack_s"][1] >= 0.15  # the healthy rank waited on rank 2
    labeled = [s for s in tel.tracer.spans()
               if s["name"] == "uplink" and (s.get("attrs") or {}).get("chaos")]
    assert [(s["rank"], s["attrs"]["chaos_delay_s"]) for s in labeled] \
        == [(2, 0.2)]
    tel.close()


def test_tracing_off_wire_and_model_identical(sim_setup, monkeypatch):
    """Acceptance: with tracing off no frame carries trace context (the
    wire is byte-identical to the pre-tracing build), and tracing on does
    not perturb training — final models match bitwise."""
    from fedml_tpu.comm.message import Message, pack_pytree
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs import Telemetry

    frames = []
    orig = Message.to_bytes
    monkeypatch.setattr(Message, "to_bytes",
                        lambda self, codec=None: frames.append(
                            f := orig(self, codec)) or f)
    agg_plain = run_simulated(*sim_setup, job_id="t-off")
    assert frames and not any(b"__trace" in f for f in frames)

    frames.clear()
    tel = Telemetry(trace=True)
    agg_traced = run_simulated(*sim_setup, job_id="t-on", telemetry=tel)
    tel.close()
    assert any(b"__trace" in f for f in frames)
    for a, b in zip(pack_pytree(agg_plain.net), pack_pytree(agg_traced.net)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_standalone_round_program_untouched_by_tracing(sim_setup):
    """The jitted round program gains nothing from tracing: identical
    metric keys (and therefore identical outputs/syncs) with the tracer on
    vs a plain telemetry bundle — tracing is host-side only."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.obs import Telemetry

    data, task, cfg = sim_setup
    tel_plain, tel_traced = Telemetry(), Telemetry(trace=True)
    m_plain = FedAvgAPI(data, task, cfg, telemetry=tel_plain).run_round(0)
    m_traced = FedAvgAPI(data, task, cfg, telemetry=tel_traced).run_round(0)
    assert set(m_plain.keys()) == set(m_traced.keys())
    spans = tel_traced.tracer.spans()
    assert {s["name"] for s in spans} >= {"pack", "round"}
    assert all(s["rank"] == 0 for s in spans)
    tel_traced.close()
    tel_plain.close()


def test_telemetry_close_writes_trace_json(sim_setup, tmp_path):
    """File-backed bundle: close() writes a Perfetto-loadable trace.json
    whose events validate against the documented schema, and report.py
    renders the critical path from the events.jsonl next to it."""
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs import Telemetry
    from fedml_tpu.obs.trace_export import validate_chrome_trace

    d = str(tmp_path)
    tel = Telemetry(log_dir=d, trace_dir=d)
    run_simulated(*sim_setup, job_id="t-file", telemetry=tel)
    tel.close()
    with open(os.path.join(d, "trace.json")) as f:
        doc = json.load(f)
    assert validate_chrome_trace(doc) == []
    assert any(e.get("name") == "local_fit" for e in doc["traceEvents"])

    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "report", os.path.join(os.path.dirname(__file__), os.pardir,
                               "scripts", "report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    rc = report.main([os.path.join(d, "events.jsonl"), "--critical-path"])
    assert rc == 0


def test_duplicate_upload_recorded_once():
    """A chaos-duplicated uplink must not double-record: the first
    delivery's arrival time and span buffer stand; the copy is ignored."""
    clock = _fixed_clock()
    tr = DistributedTracer("dup-run", clock=clock)
    tr.begin_round(0)
    ctx = tr.broadcast_ctx(1)
    tr.end_broadcast()
    buf = ClientSpanBuffer(1, clock=clock)
    buf.on_broadcast(ctx)
    with buf.span("local_fit"):
        pass
    blob = buf.upload_blob()
    tr.on_upload(1, blob)
    n = len(tr.spans())
    tr.on_upload(1, blob)  # at-least-once redelivery
    assert len(tr.spans()) == n  # no duplicated span ids
    cp = tr.finish_round()
    assert cp["slack_s"] == {1: 0.0}


def test_chaos_delay_on_downlink_attributed_to_client():
    """A delayed DOWNLINK (src = server) must be attributed to the client
    rank it slowed — the server never uploads, so src-only attribution
    would silently lose it."""
    from fedml_tpu import chaos
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.obs.tracing import chaos_delays

    plan = FaultPlan.from_json({"seed": 1, "rules": [
        {"fault": "delay", "direction": "send", "src": [0], "dst": [2],
         "delay_s": 0.2}]})
    plan.ledger.record("delay", "send", 0, 2, 0, 5)
    plan.ledger.record("straggle", "send", 1, 0, 3, 5)
    plan.ledger.record("drop", "send", 1, 0, 4, 5)  # not a delay: ignored
    chaos.install_plan(plan)
    try:
        assert chaos_delays(5) == {2: 0.2}  # straggle rule absent -> only
    finally:                                # the delay rule resolves
        chaos.install_plan(None)
    assert chaos_delays(5) == {}  # no plan installed


# ------------------------------------------------------- report rendering
def test_render_critical_path_graceful_on_old_logs():
    from fedml_tpu.obs.trace_export import render_critical_path

    out = render_critical_path([{"kind": "round", "round": 0},
                                {"kind": "eval", "round": 0}])
    assert "predates" in out  # pre-PR-3 log: notice, not a crash
    out2 = render_critical_path([{
        "kind": "round", "round": 1,
        "critical_path": {"straggler": 2, "round_s": 0.9,
                          "phases": {"local_fit": 0.5, "uplink": 0.3},
                          "slack_s": {"1": 0.25, "2": 0.0},
                          "chaos_delay_s": {"2": 0.2}}}])
    assert "rank 2 on the critical path" in out2
    assert "chaos" in out2 and "local_fit=500.0ms" in out2
    assert "rank 1=250.0ms" in out2


# ------------------------------------------------------------- liveness
def test_heartbeat_and_ranks_alive_gauges():
    from fedml_tpu.obs import comm_instrument as ci

    ci.record_rank_seen(41)
    ci.record_rank_seen("not-a-rank")  # interop peer ids must not raise
    ci.set_ranks_alive(3)
    ci.refresh_liveness()
    txt = REGISTRY.to_prometheus()
    assert "fed_ranks_alive 3.0" in txt
    assert 'fed_last_heartbeat_age_seconds{rank="41"}' in txt
    age = REGISTRY.gauge("fed_last_heartbeat_age_seconds", rank=41).value
    assert 0.0 <= age < 5.0
