#!/usr/bin/env bash
# CI entry — the reference's Travis script series
# (CI-install.sh / CI-script-fedavg.sh / CI-script-framework.sh /
# CI-script-fednas.sh / CI-script-fedavg-robust.sh) folded into one gate:
#   1. static check (parse+import, the pyflakes analogue)  — test_lint.py
#   2. unit + oracle suite on the 8-device virtual CPU mesh
#   3. standalone smoke runs across algorithm/dataset pairs (--ci 1
#      truncation, CI-script-fedavg.sh:33-38 analogue)
#   4. cross-process smoke (base framework + decentralized demo + gRPC
#      launch are inside the suite; an extra end-to-end launch here)
#
# Tiers (first arg, default smoke):
#   smoke — pytest -m smoke: every engine's oracle at minimal shapes,
#           <5 min on a 1-core box. The default so CI/driver timeboxes
#           can't turn green evidence into an rc=124.
#   full  — the whole suite (~23 min on 1 core) + the standalone smoke
#           matrix + cross-process smoke below.
set -euo pipefail
cd "$(dirname "$0")/.."
TIER="${1:-smoke}"
export PYTHONPATH="$PWD" JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

if [ "$TIER" = "smoke" ]; then
  echo "== fedlint static gate (AST invariants: jit/thread/wire discipline, docs/ANALYSIS.md) =="
  # fails the build on any NEW finding (committed grandfathered debt lives
  # annotated in scripts/fedlint_baseline.json); the --json blob is the
  # bench_gate-compatible artifact future CI can diff across commits
  mkdir -p ./tmp
  python scripts/fedlint.py --baseline scripts/fedlint_baseline.json \
    --json ./tmp/ci_fedlint_blob.json
  echo "== smoke tier (every engine oracle, minimal shapes) =="
  python -m pytest tests/ -q -m smoke
  echo "== tracing + live-health smoke (2-round loopback sim; mid-run /metrics + /healthz scrape; span-schema + Chrome-trace validation) =="
  # a stitched cross-rank trace must come out of a plain loopback sim and
  # validate against the documented span schema (docs/OBSERVABILITY.md
  # §Tracing); scripts/report.py must render its critical path. The same
  # leg now also proves the live run-health layer (§Live endpoints): a
  # scraper thread hits /metrics + /healthz over real HTTP WHILE the sim
  # runs — the new families (fed_alerts_total, fed_host_rss_bytes) must be
  # in the live text and the health status must read ok
  TRACE_DIR=./tmp/ci_trace; rm -rf "$TRACE_DIR"
  python - "$TRACE_DIR" <<'PY'
import json, os, sys, threading, time, urllib.request

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry
from fedml_tpu.obs.trace_export import validate_chrome_trace, validate_spans

d = sys.argv[1]
data = synthetic_images(num_clients=4, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
tel = Telemetry(log_dir=d, trace_dir=d, http_port=0)  # 0 = ephemeral port
scrapes, stop = [], threading.Event()

def scraper():
    while not stop.is_set():
        try:
            prom = urllib.request.urlopen(tel.httpd.url("/metrics"),
                                          timeout=2).read().decode()
            hz = json.loads(urllib.request.urlopen(
                tel.httpd.url("/healthz"), timeout=2).read())
            scrapes.append((prom, hz))
        except OSError:
            pass
        time.sleep(0.05)

t = threading.Thread(target=scraper, daemon=True)
t.start()
run_simulated(data, classification_task(LogisticRegression(num_classes=3)),
              FedAvgConfig(comm_round=2, client_num_in_total=4,
                           client_num_per_round=2, batch_size=6, lr=0.1,
                           frequency_of_the_test=1),
              job_id="ci-trace-smoke", telemetry=tel)
stop.set(); t.join(timeout=5)
assert scrapes, "no successful mid-run scrape"
prom, hz = scrapes[-1]
for fam in ("fed_alerts_total", "fed_host_rss_bytes"):
    assert fam in prom, f"{fam} missing from the live /metrics scrape"
assert hz["status"] == "ok", f"/healthz not ok mid-run: {hz}"
assert hz["run"] and hz["port"] == tel.http_port
errs = validate_spans(tel.tracer.spans())
assert not errs, f"span schema violations: {errs}"
tel.close()
with open(os.path.join(d, "trace.json")) as f:
    doc = json.load(f)
errs = validate_chrome_trace(doc)
assert not errs, f"chrome trace violations: {errs}"
rounds = [json.loads(line) for line in open(os.path.join(d, "events.jsonl"))
          if '"round"' in line]
cps = [r.get("critical_path") for r in rounds if r.get("kind") == "round"]
assert cps and all(cps), "round records missing critical_path"
print(f"tracing + live-health smoke ok: {len(doc['traceEvents'])} events, "
      f"straggler ranks {[c['straggler'] for c in cps]}, "
      f"{len(scrapes)} live scrapes, status {hz['status']}")
PY
  python scripts/report.py "$TRACE_DIR/events.jsonl" --critical-path --alerts
  echo "== bench regression gate (smoke blob vs committed tolerances) =="
  # the smoke leg's event log doubles as a bench artifact: report.py folds
  # it into a BENCH blob and bench_gate.py compares it against the
  # committed tolerance file — a PR that tanks the smoke run's structure
  # or accuracy (or its throughput by an order of magnitude) fails here
  # instead of drifting silently (docs/OBSERVABILITY.md §Bench gate)
  python scripts/report.py "$TRACE_DIR/events.jsonl" \
    --bench-json ./tmp/ci_trace_blob.json
  python scripts/bench_gate.py ./tmp/ci_trace_blob.json \
    --gate scripts/ci_bench_gate.json
  echo "== byzantine smoke (2-round loopback: 1 sign-flip adversary vs krum) =="
  # the robust-aggregation gate must quarantine the attacker (non-empty
  # ledger) and the defended run must stay finite (docs/ROBUSTNESS.md
  # §Byzantine-robust aggregation)
  python - <<'PY'
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.chaos import AdversaryPlan
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression

data = synthetic_images(num_clients=8, image_shape=(8, 8, 1), num_classes=4,
                        samples_per_client=24, test_samples=96, seed=3)
plan = AdversaryPlan.from_json(
    {"seed": 5, "rules": [{"attack": "sign_flip", "ranks": [2],
                           "factor": 10.0}]})
agg = run_simulated(data, classification_task(LogisticRegression(num_classes=4)),
                    FedAvgConfig(comm_round=2, client_num_in_total=8,
                                 client_num_per_round=8, batch_size=8,
                                 lr=0.1, frequency_of_the_test=1),
                    job_id="ci-byz-smoke", adversary_plan=plan,
                    aggregator="krum", aggregator_params={"f": 2})
ledger = agg.quarantine.canonical()
assert ledger, "quarantine ledger empty: the adversary went undetected"
assert any(e[1] == 2 for e in ledger), f"rank 2 never quarantined: {ledger}"
assert all(np.isfinite(np.asarray(v)).all() for v in pack_pytree(agg.net))
print(f"byzantine smoke ok: {len(ledger)} quarantine entries, "
      f"counts {agg.quarantine.counts()}, final eval {agg.history[-1]}")
PY
  echo "== pipeline smoke (3-round pipelined runs; prefetch/dispatch metrics in the Prometheus export) =="
  # the pipelined driver (docs/PERFORMANCE.md) must (a) reproduce the
  # synchronous driver's model bits over a 3-round run, (b) exercise the
  # loopback sender worker + decode-on-arrival path, and (c) export the
  # new metric families (fed_h2d_seconds / fed_prefetch_stall_seconds /
  # fed_dispatch_depth) through Telemetry.close()'s metrics.prom
  PIPE_DIR=./tmp/ci_pipeline; rm -rf "$PIPE_DIR"
  python - "$PIPE_DIR" <<'PY'
import os, sys

import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry

d = sys.argv[1]
data = synthetic_images(num_clients=4, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=3, client_num_in_total=4,
                   client_num_per_round=2, batch_size=6,
                   frequency_of_the_test=100)
# loopback leg: async uplink sender + decode-on-arrival staging
run_simulated(data, task, cfg, job_id="ci-pipe-smoke", warmup=True)
# standalone leg: 3 pipelined rounds vs the synchronous driver, bit-for-bit
tel = Telemetry(log_dir=d)
a = FedAvgAPI(data, task, cfg)
for r in range(3):
    a.run_round(r)
b = FedAvgAPI(data, task, cfg, prefetch=2, telemetry=tel)
b.run_pipelined(0, 3)
import jax
pa, pb = jax.tree.leaves(a.net.params), jax.tree.leaves(b.net.params)
assert all(np.array_equal(np.asarray(x), np.asarray(y))
           for x, y in zip(pa, pb)), "pipelined run diverged from synchronous"
tel.close()
prom = open(os.path.join(d, "metrics.prom")).read()
for fam in ("fed_h2d_seconds", "fed_prefetch_stall_seconds",
            "fed_dispatch_depth"):
    assert fam in prom, f"{fam} missing from the Prometheus export"
print("pipeline smoke ok: 3 pipelined rounds bit-identical, "
      "metric families exported")
PY
  python scripts/report.py "$PIPE_DIR/events.jsonl"
  echo "== goodput + run-store smoke (pipeline A/B; fed_goodput_* families; runstore diff names the moved bucket; committed gate) =="
  # the round-economics plane (docs/PERFORMANCE.md §Round economics) must
  # (a) decompose every telemetry round into exclusive buckets that sum to
  # the round wall, (b) export the fed_goodput_*/fed_duty_cycle families
  # through the Prometheus text, and (c) attribute a pipeline on/off A/B
  # to the bucket pipelining actually moves: the sync driver's serial pack
  # IS its prefetch stall, so `runstore diff` must name prefetch_stall as
  # the moved bucket — and the pipelined leg must pass the committed
  # tolerance file (docs/OBSERVABILITY.md §Run-store)
  GOOD_DIR=./tmp/ci_goodput; rm -rf "$GOOD_DIR" ./tmp/ci_goodput_index.jsonl
  python - "$GOOD_DIR" <<'PY'
import json, os, sys

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry

d = sys.argv[1]
# pack-heavy workload: 4x512 CIFAR-shaped clients -> ~25 MB packed per
# round, so the sync pack (= prefetch stall) sits far above compute noise
data = synthetic_images(num_clients=4, image_shape=(32, 32, 3),
                        num_classes=5, samples_per_client=512,
                        test_samples=32, seed=0)
task = classification_task(LogisticRegression(num_classes=5))
cfg = FedAvgConfig(comm_round=8, client_num_in_total=4,
                   client_num_per_round=4, batch_size=64, lr=0.1,
                   epochs=2, frequency_of_the_test=100)
# A: synchronous rounds — the serial pack IS the prefetch stall
tel_a = Telemetry(log_dir=os.path.join(d, "a"))
a = FedAvgAPI(data, task, cfg, telemetry=tel_a)
a.warmup()
for r in range(8):
    a.run_round(r)
tel_a.close()
# B: pipelined — pack overlaps on the prefetch thread, the stall shrinks
tel_b = Telemetry(log_dir=os.path.join(d, "b"))
b = FedAvgAPI(data, task, cfg, prefetch=2, telemetry=tel_b)
b.warmup()
b.run_pipelined(0, 8)
tel_b.close()
prom = open(os.path.join(d, "b", "metrics.prom")).read()
for fam in ("fed_duty_cycle", "fed_goodput_flops_per_sec",
            "fed_goodput_rounds_total", "fed_xla_variant_compiles_total"):
    assert fam in prom, f"{fam} missing from the Prometheus export"
recs = [json.loads(line)
        for line in open(os.path.join(d, "a", "events.jsonl"))]
gp = [r["goodput"] for r in recs
      if r.get("kind") == "round" and r.get("goodput")]
assert gp, "sync rounds carry no goodput block"
for g in gp:
    s = sum(g["buckets"].values())
    assert abs(s - g["wall_s"]) < 1e-6 + 1e-3 * g["wall_s"], (s, g["wall_s"])
print("goodput smoke ok: buckets sum to wall on all "
      f"{len(gp)} sync rounds, families exported")
PY
  python scripts/report.py "$GOOD_DIR/b/events.jsonl" --compiles
  python scripts/runstore.py --index ./tmp/ci_goodput_index.jsonl ingest \
    "$GOOD_DIR/a/events.jsonl" "$GOOD_DIR/b/events.jsonl"
  python scripts/runstore.py --index ./tmp/ci_goodput_index.jsonl \
    diff a/events.jsonl b/events.jsonl | tee ./tmp/ci_goodput_diff.txt
  grep -q "moved bucket: prefetch_stall" ./tmp/ci_goodput_diff.txt || {
    echo "goodput A/B did not attribute the pipeline delta to prefetch_stall"
    exit 1
  }
  python scripts/runstore.py --index ./tmp/ci_goodput_index.jsonl \
    gate b/events.jsonl --gate scripts/ci_goodput_gate.json
  echo "== sharded-aggregation smoke (forced 4-device mesh: sharded ≡ replicated; fed_agg_bytes/fed_server_state_bytes exported) =="
  # the partitioned server state (docs/PERFORMANCE.md §Partitioned server
  # state) must (a) reproduce the replicated mesh path's model bits AND
  # quarantine ledger on a forced multi-device host mesh, (b) report
  # per-device server-state bytes that actually shrink vs replicated, and
  # (c) export the new metric families through Telemetry.close()
  SHARD_DIR=./tmp/ci_sharded; rm -rf "$SHARD_DIR"
  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  python - "$SHARD_DIR" <<'PY'
import os, sys

import numpy as np

import jax
from jax.sharding import Mesh

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_lr
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry

d = sys.argv[1]
assert jax.device_count() == 4, jax.device_count()
mesh = Mesh(np.array(jax.devices()), ("clients",))
data = synthetic_lr(num_clients=8, dim=20, num_classes=5, seed=0)
task = classification_task(LogisticRegression(num_classes=5))
cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                   client_num_per_round=4, batch_size=16, lr=0.05,
                   max_batches=4, frequency_of_the_test=100)
# a tight norm gate quarantines natural outliers -> non-vacuous ledgers
kw = dict(aggregator="median", sanitize=0.9)
a = FedAvgAPI(data, task, cfg, mesh=mesh, **kw)
for r in range(3):
    a.run_round(r)
tel = Telemetry(log_dir=d)
b = FedAvgAPI(data, task, cfg, mesh=mesh, shard_server_state=True,
              telemetry=tel, **kw)
for r in range(3):
    b.run_round(r)
for x, y in zip(jax.tree.leaves(a.net.params), jax.tree.leaves(b.net.params)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                  err_msg="sharded diverged from replicated")
assert a.quarantine.canonical() == b.quarantine.canonical()
kern = [v for v in jax.tree.leaves(b.net.params) if v.ndim == 2][0]
assert not kern.is_fully_replicated, "kernel never partitioned"
tel.close()
prom = open(os.path.join(d, "metrics.prom")).read()
for fam in ("fed_agg_bytes_total", "fed_server_state_bytes"):
    assert fam in prom, f"{fam} missing from the Prometheus export"
rep = [float(l.split()[-1]) for l in prom.splitlines()
       if l.startswith('fed_server_state_bytes{placement="replicated"}')][0]
sh = [float(l.split()[-1]) for l in prom.splitlines()
      if l.startswith('fed_server_state_bytes{placement="sharded"}')][0]
assert sh < rep, f"sharded per-device bytes {sh} not below replicated {rep}"
print(f"sharded-aggregation smoke ok: 3 rounds bit-identical, ledger "
      f"{len(b.quarantine.canonical())} entries, per-device bytes "
      f"{sh:.0f} vs {rep:.0f} replicated")
PY
  echo "== async buffered smoke (K=cohort bitwise ≡ sync; straggler A/B: async < sync wall-clock; staleness/shed metrics exported) =="
  # buffered-async rounds (docs/ROBUSTNESS.md §Asynchronous buffered
  # rounds) must (a) reduce bitwise to the synchronous path at K=cohort /
  # staleness bound 0 (model bits AND quarantine ledger), (b) complete the
  # same number of global updates in less wall-clock than the sync barrier
  # under a seeded 1-rank straggle plan while still converging, and (c)
  # export the new metric families through Telemetry.close()
  ASYNC_DIR=./tmp/ci_async; rm -rf "$ASYNC_DIR"
  python - "$ASYNC_DIR" <<'PY'
import os, sys, time

import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.chaos import FaultPlan
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry

d = sys.argv[1]
data = synthetic_images(num_clients=8, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=48, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                   client_num_per_round=4, batch_size=6, lr=0.1,
                   frequency_of_the_test=100)
# standalone leg: K=cohort / bound 0 bitwise ≡ the run_round loop, with the
# sanitation gate armed so the quarantine ledgers are non-vacuous
kw = dict(aggregator="median", sanitize=0.9)
a = FedAvgAPI(data, task, cfg, **kw)
for r in range(3):
    a.run_round(r)
b = FedAvgAPI(data, task, cfg, **kw)
b.run_async(3, buffer_k=4, staleness="constant", staleness_bound=0)
import jax
for x, y in zip(jax.tree.leaves(a.net.params), jax.tree.leaves(b.net.params)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                  err_msg="async K=cohort diverged from sync")
assert a.quarantine.canonical() == b.quarantine.canonical()
assert len(b.quarantine.canonical()) > 0
# cross-process leg: seeded 1-rank straggler; async completes the same
# number of global updates in measurably less wall-clock than the barrier
cfg2 = FedAvgConfig(comm_round=4, client_num_in_total=8,
                    client_num_per_round=3, batch_size=6, lr=0.1,
                    frequency_of_the_test=1)
run_simulated(data, task, cfg2, job_id="ci-async-warm")  # compile leg
plan = lambda: FaultPlan.from_json({"seed": 3, "rules": [
    {"fault": "straggle", "src": [2], "dst": [0], "delay_s": 0.25}]})
t0 = time.perf_counter()
s = run_simulated(data, task, cfg2, job_id="ci-async-s", chaos_plan=plan(),
                  round_timeout_s=5.0)
sync_t = time.perf_counter() - t0
tel = Telemetry(log_dir=d)
t0 = time.perf_counter()
asy = run_simulated(data, task, cfg2, job_id="ci-async-a", chaos_plan=plan(),
                    round_timeout_s=5.0, async_buffer_k=2,
                    staleness="poly:0.5", telemetry=tel)
async_t = time.perf_counter() - t0
assert asy.history and asy.history[-1]["round"] == 3, asy.history[-1:]
assert async_t < sync_t, f"async {async_t:.2f}s not below sync {sync_t:.2f}s"
assert float(asy.history[-1]["test_acc"]) >= 0.9, asy.history[-1]
tel.close()
prom = open(os.path.join(d, "metrics.prom")).read()
for fam in ("fed_buffer_fill_seconds", "fed_update_staleness",
            "fed_async_shed_total"):
    assert fam in prom, f"{fam} missing from the Prometheus export"
print(f"async buffered smoke ok: K=cohort bitwise (ledger "
      f"{len(b.quarantine.canonical())} entries), straggler A/B "
      f"{sync_t:.2f}s sync vs {async_t:.2f}s async, families exported")
PY
  python scripts/report.py "$ASYNC_DIR/events.jsonl"
  echo "== wire-codec smoke (delta+int8 round-trip parity; quantized garbage quarantines; comm_bytes_total{direction} exported) =="
  # the wire-efficiency layer (docs/PERFORMANCE.md §Wire efficiency) must
  # (a) round-trip the delta+int8 tier (encode/decode oracle + a loopback
  # run that matches the dense protocol within the EF tolerance), (b)
  # quarantine decoded quantized garbage (a NaN client under delta-int8
  # must die at the sanitation gate, never poison the aggregate), and (c)
  # export the per-direction byte accounting through Telemetry.close()
  CODEC_DIR=./tmp/ci_codec; rm -rf "$CODEC_DIR"
  python - "$CODEC_DIR" <<'PY'
import os, sys

import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.chaos import AdversaryPlan
from fedml_tpu.comm.delta import (apply_delta, decode_update, encode_update,
                                  round_delta)
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry

d = sys.argv[1]
# (a) numpy round-trip oracle: delta -> int8 -> decode within half a step
rs = np.random.RandomState(0)
local = [rs.randn(32, 8).astype(np.float32), np.arange(4, dtype=np.int64)]
base = [rs.randn(32, 8).astype(np.float32), np.zeros(4, np.int64)]
delta = round_delta(local, base)
payload, scales = encode_update(delta, "delta-int8", deadzone=0.0)
dec = decode_update(payload, scales, "delta-int8", base)
assert np.max(np.abs(dec[0] - delta[0])) <= scales[0] / 2 + 1e-7
np.testing.assert_array_equal(apply_delta(base, dec)[1], local[1])
data = synthetic_images(num_clients=8, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=48, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                   client_num_per_round=4, batch_size=6, lr=0.1,
                   frequency_of_the_test=1)
tel = Telemetry(log_dir=d)
a = run_simulated(data, task, cfg, job_id="ci-codec-dense", telemetry=tel)
b = run_simulated(data, task, cfg, job_id="ci-codec-q8",
                  update_codec="delta-int8")
for x, y in zip(pack_pytree(a.net), pack_pytree(b.net)):
    # matched rounds, EF tolerance: int8+EF stays in the dense ballpark
    assert float(np.max(np.abs(np.asarray(x) - np.asarray(y)))) < 0.15
assert b.history[-1]["test_acc"] >= 0.9, b.history[-1]
# (b) a NaN upload under the quantized tier quarantines at the gate
plan = AdversaryPlan.from_json(
    {"seed": 1, "rules": [{"attack": "nan", "ranks": [2]}]})
g = run_simulated(data, task, cfg, job_id="ci-codec-nan",
                  update_codec="delta-int8", adversary_plan=plan)
led = g.quarantine.canonical()
assert led and any(e[1] == 2 for e in led), f"NaN client not quarantined: {led}"
assert all(np.isfinite(np.asarray(v)).all() for v in pack_pytree(g.net))
tel.close()
prom = open(os.path.join(d, "metrics.prom")).read()
assert "comm_bytes_total" in prom, "comm_bytes_total missing from export"
for direction in ("uplink", "downlink"):
    assert f'direction="{direction}"' in prom, \
        f"direction={direction} missing from comm_bytes_total"
print(f"wire-codec smoke ok: int8 round-trip within half a step, NaN "
      f"quarantined ({g.quarantine.counts()}), directions exported")
PY
  python scripts/report.py "$CODEC_DIR/events.jsonl"
  echo "== fused-aggregation smoke (delta-int8 + NaN adversary: fused == stacked ledger, no host densify; flush metrics exported) =="
  # fused on-device aggregation (docs/PERFORMANCE.md §Fused aggregation)
  # must (a) reproduce the stacked pairwise route's quarantine ledger under
  # a delta-int8 uplink with a NaN adversary (the poison dies at the
  # IN-GRAPH gate), (b) never touch the host densify path (apply_delta /
  # topk_decode raise if called — the client-side EF residual uses
  # decode_update, which stays live), and (c) export the new
  # fed_flush_seconds / fed_agg_stack_bytes{mode} families
  python - <<'PY'
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.chaos import AdversaryPlan
from fedml_tpu.comm import delta as delta_mod
from fedml_tpu.comm import sparse as sparse_mod
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs.metrics import REGISTRY

data = synthetic_images(num_clients=8, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                   client_num_per_round=4, batch_size=6, lr=0.1,
                   frequency_of_the_test=1)
adv = lambda: AdversaryPlan.from_json(
    {"seed": 1, "rules": [{"attack": "nan", "ranks": [2]}]})
stacked = run_simulated(data, task, cfg, job_id="ci-fused-stacked",
                        sum_assoc="pairwise", update_codec="delta-int8",
                        adversary_plan=adv())
# the fused leg must never host-densify: the server-side decoders raise
real_apply, real_topk = delta_mod.apply_delta, sparse_mod.topk_decode
def _boom(*a, **kw):
    raise AssertionError("host densify called on the fused path")
delta_mod.apply_delta = _boom
sparse_mod.topk_decode = _boom
try:
    fused = run_simulated(data, task, cfg, job_id="ci-fused", fused_agg=True,
                          update_codec="delta-int8", adversary_plan=adv())
finally:
    delta_mod.apply_delta, sparse_mod.topk_decode = real_apply, real_topk
led = fused.quarantine.canonical()
assert led == stacked.quarantine.canonical() and led, led
assert any(e[2] == "nonfinite" and e[1] == 2 for e in led), led
for x, y in zip(pack_pytree(stacked.net), pack_pytree(fused.net)):
    # host vs device int8 dequant: identical up to the fma ulp (the
    # lossless tiers are bitwise — tier-1's parity battery pins both)
    assert float(np.max(np.abs(np.asarray(x) - np.asarray(y)))) < 1e-6
assert all(np.isfinite(np.asarray(v)).all() for v in pack_pytree(fused.net))
snap = REGISTRY.snapshot()
assert "fed_flush_seconds" in snap, sorted(snap)
modes = snap.get("fed_agg_stack_bytes", {})
assert any("mode=fused" in k for k in modes) and \
    any("mode=stacked" in k for k in modes), modes
# PR-21 universal ingest: fused×median×delta-int8 under a 2-of-8
# sign-flip adversary — the STAGED fused route (per-arrival evidence
# rows, one verdict-composition flush jit) reproduces the stacked
# pairwise verdict path: ledger bitwise, model within the delta-int8
# fma ulp (lossless tiers are bitwise — tier-1 pins them), and the
# median actually outvoted the flipped pair (finite, converged model)
cfg8 = FedAvgConfig(comm_round=3, client_num_in_total=8,
                    client_num_per_round=8, batch_size=6, lr=0.1,
                    frequency_of_the_test=1)
flip = lambda: AdversaryPlan.from_json(
    {"seed": 2, "rules": [{"attack": "sign_flip", "ranks": [2, 5],
                           "factor": 3.0}]})
rs = run_simulated(data, task, cfg8, job_id="ci-fused-rob-s",
                   sum_assoc="pairwise", aggregator="median",
                   update_codec="delta-int8", adversary_plan=flip())
rf = run_simulated(data, task, cfg8, job_id="ci-fused-rob-f",
                   fused_agg=True, aggregator="median",
                   update_codec="delta-int8", adversary_plan=flip())
assert rf.quarantine.canonical() == rs.quarantine.canonical()
for x, y in zip(pack_pytree(rs.net), pack_pytree(rf.net)):
    assert float(np.max(np.abs(np.asarray(x) - np.asarray(y)))) < 1e-6
assert all(np.isfinite(np.asarray(v)).all() for v in pack_pytree(rf.net))
modes2 = REGISTRY.snapshot().get("fed_agg_stack_bytes", {})
assert any("mode=fused_staged" in k for k in modes2), modes2
print(f"fused-aggregation smoke ok: ledger {len(led)} entries equal, "
      f"no host densify, fused×median ≡ stacked×median under 2-of-8 "
      f"sign-flip, stack bytes {modes2}")
PY
  echo "== secure-aggregation + privacy smoke (masked == plain within tolerance; mid-run dropout recovers; fed_privacy_epsilon exported) =="
  # the masked secure-aggregation tier (docs/ROBUSTNESS.md §Secure
  # aggregation) must (a) match plain FedAvg within quantization on a
  # clean run, (b) RECOVER a mid-run
  # dropout (chaos drop on one rank's uplink -> reveal round-trip ->
  # elastic partial, ledgered secagg_dropout), and (c) carry the privacy
  # ledger end to end in dp mode: privacy block on every round record,
  # fed_privacy_epsilon + fed_secagg_rounds_total in the Prometheus text
  python - <<'PY'
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.chaos import FaultPlan
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed import turboaggregate as ta
from fedml_tpu.distributed.fedavg import run_simulated as plain_run
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs.metrics import REGISTRY

data = synthetic_images(num_clients=8, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                   client_num_per_round=3, batch_size=6, lr=0.1,
                   frequency_of_the_test=1)
plain = plain_run(data, task, cfg, job_id="ci-secagg-plain")
masked = ta.run_simulated(data, task, cfg, job_id="ci-secagg")
for x, y in zip(pack_pytree(plain.net), pack_pytree(masked.net)):
    assert float(np.max(np.abs(np.asarray(x, np.float64)
                               - np.asarray(y, np.float64)))) < 5e-3
# mid-run dropout: rank 2's round-1 uplink is dropped once -> the server
# recovers via the reveal round-trip and ledgers the slot
plan = FaultPlan.from_json({"seed": 3, "rules": [
    {"fault": "drop", "direction": "send", "src": [2], "dst": [0],
     "rounds": [1, 2], "max_per_link": 1}]})
# threshold_t=1: a 3-slot cohort tolerates one dropout (2 survivors >=
# t+1); the default t=2 would shed instead of recovering here
rec = ta.run_simulated(data, task, cfg, job_id="ci-secagg-drop",
                       chaos_plan=plan, round_timeout_s=3.0,
                       threshold_t=1)
led = rec.quarantine.canonical()
assert any(e[2] == "secagg_dropout" and e[1] == 2 for e in led), led
assert rec.history and rec.history[-1]["round"] == cfg.comm_round - 1
# dp mode: privacy ledger end to end
dp = ta.run_simulated(data, task, cfg, job_id="ci-secagg-dp",
                      defense_type="dp", noise_multiplier=1.0,
                      norm_bound=0.5)
block = dp.privacy_record()
assert block and block["eps"] > 0 and block["z"] == 1.0, block
prom = REGISTRY.to_prometheus()
assert "fed_privacy_epsilon" in prom and "fed_secagg_rounds_total" in prom
snap = REGISTRY.snapshot()
outcomes = snap.get("fed_secagg_rounds_total", {})
assert outcomes.get("outcome=recovered", 0) >= 1, outcomes
print(f"secure-aggregation smoke ok: masked == plain, dropout recovered "
      f"(ledger {len(led)} entries), eps={block['eps']:.3f} exported")
PY
  echo "== hierarchical masked secagg smoke (2 edges x 4 workers; seeded in-block dropout -> edge-local reveal; per-client eps family exported; report renders eps_cli) =="
  # the masked tier composed with the tree (docs/ROBUSTNESS.md
  # §Hierarchical secure aggregation) must (a) run a dp 2-tier masked
  # campaign where a seeded in-block crash recovers via the EDGE-LOCAL
  # reveal (secagg_dropout ledgered at cohort rank, outcome=recovered,
  # root ingress O(edges) through the recovery), and (b) carry the
  # per-client privacy ledger end to end: eps_client_max on the round
  # records, the fed_privacy_client_epsilon{stat} family next to
  # fed_privacy_epsilon in the Prometheus export, and report.py's
  # eps_cli column (hidden on pre-ledger logs)
  HSA_DIR=./tmp/ci_hier_secagg; rm -rf "$HSA_DIR"
  python - "$HSA_DIR" <<'PY'
import sys

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.chaos import FaultPlan
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed import turboaggregate as ta
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry

d = sys.argv[1]
data = synthetic_images(num_clients=8, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                   client_num_per_round=8, batch_size=6, lr=0.1,
                   frequency_of_the_test=1)
# worker rank 4 = slot 1 (edge 0's block) dark in round 1: the edge
# strips its orphaned masks locally and forwards a recovered partial
plan = FaultPlan.from_json({"seed": 7, "rules": [
    {"fault": "crash", "ranks": [4], "rounds": [1, 2]}]})
tel = Telemetry(log_dir=d)
agg = ta.run_simulated(data, task, cfg, job_id="ci-hsa", edges=2,
                       defense_type="dp", noise_multiplier=1.0,
                       norm_bound=0.5, chaos_plan=plan,
                       round_timeout_s=3.0, telemetry=tel)
tel.close()
assert agg.history and agg.history[-1]["round"] == 1, agg.history[-1:]
led = agg.quarantine.canonical()
drops = [e for e in led if e[2] == "secagg_dropout"]
assert drops and {(e[0], e[1]) for e in drops} == {(1, 2)}, led
assert not any(e[2] == "secagg_shed" for e in led), led  # edge-LOCAL heal
assert agg.fanin_history == [2, 2], agg.fanin_history  # O(edges) ingress
block = agg.privacy_record()
assert block and block["eps_client_max"] > 0 \
    and block["clients_charged"] >= 7, block
import os
prom = open(os.path.join(d, "metrics.prom")).read()
assert "fed_privacy_epsilon" in prom, "cohort eps gauge missing"
for stat in ("max", "mean", "count"):
    assert f'fed_privacy_client_epsilon{{stat="{stat}"}}' in prom, \
        f"per-client eps stat={stat} missing from the export"
assert 'fed_secagg_rounds_total{outcome="recovered"}' in prom
print(f"hierarchical masked secagg smoke ok: in-block dropout recovered "
      f"edge-locally (ledger {led}), fan-in {agg.fanin_history}, "
      f"eps_client_max={block['eps_client_max']} over "
      f"{block['clients_charged']} clients")
PY
  python scripts/report.py "$HSA_DIR/events.jsonl" | tee ./tmp/ci_hsa_report.txt
  grep -q "eps_cli" ./tmp/ci_hsa_report.txt \
    || { echo "report.py did not render the eps_cli column"; exit 1; }
  echo "== flat-memory streamed smoke (100k-virtual-client PackedNpySource run; fed_host_rss_bytes flat across rounds, gated via bench_gate.py) =="
  # the streamed data plane (docs/PERFORMANCE.md §Streaming & cohort
  # bucketing) must hold host RSS FLAT in population size: a 100k-client
  # packed-npy population is generated chunked (the writer never
  # materializes it either), the engine runs size-bucketed cohorts over
  # the lazy source with memwatch telemetry on, and the round records'
  # fed_host_rss_bytes samples are gated — growth across rounds beyond a
  # few percent (or a dataset-sized jump = someone re-materialized the
  # population) fails CI, not a human eyeballing a chart
  STREAM_DIR=./tmp/ci_stream; rm -rf "$STREAM_DIR"
  python - "$STREAM_DIR" <<'PY'
import json, os, sys

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core.client_source import PackedNpySource
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_packed_population
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import Telemetry

d = sys.argv[1]
N, DIM, ROUNDS = 100_000, 16, 12
# the one fixture writer: chunked, so the writer's RSS stays flat too, and labels correlate with the rows
# actually written
data_dir = synthetic_packed_population(os.path.join(d, "packed"), N,
                                       dim=DIM)
src = PackedNpySource(data_dir)
tel = Telemetry(log_dir=d, memwatch=True)
cfg = FedAvgConfig(comm_round=ROUNDS, client_num_in_total=N,
                   client_num_per_round=16, batch_size=8, lr=0.1,
                   frequency_of_the_test=10_000, seed=0)
api = FedAvgAPI(src, task := classification_task(
    LogisticRegression(num_classes=5)), cfg, bucket_batches=True,
    telemetry=tel)
rep = api.warmup()  # all bucket variants AOT — compile RSS paid up front
api.train(ROUNDS)   # train() also emits the run header (dataset_source)
tel.close()
recs = [json.loads(line) for line in open(os.path.join(d, "events.jsonl"))]
hdr = [r for r in recs if r.get("kind") == "run"][0]
assert hdr["dataset_source"] == "synthetic", hdr
rss = [r["mem"]["host_rss_bytes"] for r in recs
       if r.get("kind") == "round" and "mem" in r]
assert len(rss) == ROUNDS, f"expected {ROUNDS} memwatch samples, got {len(rss)}"
packs = [r["pack"] for r in recs if r.get("kind") == "round"]
assert any(p["bucket_B"] < p["budget_B"] for p in packs), \
    f"bucketing never engaged: {packs[:3]}"
base = rss[2]  # post-warm reference (rounds 0-1 absorb first dispatches)
blob = {
    "metric": "stream_rss_growth_ratio",
    "value": round(max(rss[2:]) / base, 4),
    "unit": "max_rss/post_warm_rss",
    "stream_rss_growth_ratio": round(max(rss[2:]) / base, 4),
    "stream_rss_growth_bytes": int(max(rss[2:]) - base),
    "stream_clients": N,
    "stream_rounds": ROUNDS,
    "rss_post_warm_bytes": int(base),
    "rss_end_bytes": int(rss[-1]),
    "warmup_variants": rep.get("variants"),
}
with open("./tmp/ci_stream_blob.json", "w") as f:
    json.dump(blob, f, indent=2)
src.close()
print(f"flat-memory streamed smoke ok: {N} clients, rss "
      f"{base/1e6:.0f}MB -> {rss[-1]/1e6:.0f}MB over {ROUNDS} rounds, "
      f"growth ratio {blob['stream_rss_growth_ratio']}, "
      f"buckets {sorted({p['bucket_B'] for p in packs})}")
PY
  python scripts/bench_gate.py ./tmp/ci_stream_blob.json \
    --gate scripts/ci_stream_gate.json
  python scripts/report.py "$STREAM_DIR/events.jsonl"
  echo "== hierarchical 2-tier smoke (1 root + 2 edges + 8 workers; tree == flat pairwise, bitwise; root fan-in == edges) =="
  # the edge-aggregation tier (docs/ROBUSTNESS.md §Hierarchical tiers)
  # must reproduce the flat pairwise run's model bits AND quarantine
  # ledger under seeded chaos with a NaN adversary in the cohort, with
  # the root folding exactly E pre-aggregated partials per round
  python - <<'PY'
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.chaos import AdversaryPlan, FaultPlan
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression

data = synthetic_images(num_clients=8, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                   client_num_per_round=8, batch_size=6, lr=0.1,
                   frequency_of_the_test=1)
E = 2
# ONE plan drives both topologies: adversary ranks are cohort ranks
# (tree workers match by slot + 1)
adv = lambda: AdversaryPlan.from_json(
    {"seed": 1, "rules": [{"attack": "nan", "ranks": [3]}]})
chaos = lambda: FaultPlan.from_json({"seed": 7, "rules": [
    {"fault": "delay", "delay_s": 0.05, "prob": 0.5},
    {"fault": "duplicate", "prob": 0.3}]})
flat = run_simulated(data, task, cfg, job_id="ci-hier-flat",
                     sum_assoc="pairwise", adversary_plan=adv(),
                     chaos_plan=chaos(), round_timeout_s=15.0)
tree = run_simulated(data, task, cfg, job_id="ci-hier-tree", edges=E,
                     adversary_plan=adv(), chaos_plan=chaos(),
                     round_timeout_s=15.0)
for x, y in zip(pack_pytree(flat.net), pack_pytree(tree.net)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                  err_msg="tree diverged from flat")
assert tree.fanin_history == [E] * 3, tree.fanin_history
led = tree.quarantine.canonical()
assert led == flat.quarantine.canonical() and led, led
assert all(np.isfinite(np.asarray(v)).all() for v in pack_pytree(tree.net))
print(f"hierarchical smoke ok: tree == flat bitwise over {cfg.comm_round} "
      f"rounds, fan-in {tree.fanin_history}, ledger {len(led)} entries "
      f"(NaN adversary quarantined at the edge)")
PY
  echo "== cross-tier robust gating smoke (2-tier + median vs a 2-of-8 sign-flip; tree == flat bits + ledger; evidence/verdict bytes exported) =="
  # the two-phase protocol (docs/ROBUSTNESS.md §Cross-tier robust gating):
  # a robust estimator composes with --edges — the root gates over
  # edge-forwarded evidence and returns verdicts, so root ingress stays
  # O(edges) update frames while the ledger matches a flat two-phase run
  # entry-for-entry; the control plane's bytes are visible (and bounded)
  # in comm_bytes_total{direction=evidence|verdict}
  python - <<'PY'
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.chaos import AdversaryPlan
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.robust_agg import EVIDENCE_SKETCH_DIM
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs.metrics import REGISTRY

data = synthetic_images(num_clients=8, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
task = classification_task(LogisticRegression(num_classes=3))
cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                   client_num_per_round=8, batch_size=6, lr=0.1,
                   frequency_of_the_test=1)
E, W = 2, 8
adv = lambda: AdversaryPlan.from_json({"seed": 1, "rules": [
    {"attack": "sign_flip", "ranks": [2, 5], "factor": 10.0}]})
flat = run_simulated(data, task, cfg, job_id="ci-xtier-flat",
                     sum_assoc="pairwise", aggregator="median",
                     adversary_plan=adv())
tree = run_simulated(data, task, cfg, job_id="ci-xtier-tree", edges=E,
                     aggregator="median", adversary_plan=adv())
for x, y in zip(pack_pytree(flat.net), pack_pytree(tree.net)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                  err_msg="tree-median diverged from flat")
led = tree.quarantine.canonical()
assert led == flat.quarantine.canonical() and led, led
assert {e[1] for e in led if e[2] == "norm_outlier"} == {2, 5}, led
assert tree.fanin_history == [E] * cfg.comm_round, tree.fanin_history
fam = REGISTRY.snapshot().get("comm_bytes_total", {})
ev_b = sum(v for k, v in fam.items() if "direction=evidence" in k)
vd_b = sum(v for k, v in fam.items() if "direction=verdict" in k)
assert ev_b > 0 and vd_b > 0, sorted(fam)
budget = cfg.comm_round * (W * 4 * (EVIDENCE_SKETCH_DIM + 3) + E * 2048)
assert ev_b <= budget, (ev_b, budget)
print(f"cross-tier robust smoke ok: tree-median == flat bitwise, "
      f"{len(led)} ledger entries (sign-flippers quarantined), fan-in "
      f"{tree.fanin_history}, evidence {int(ev_b)}B / verdict {int(vd_b)}B "
      f"over {cfg.comm_round} rounds (budget {budget}B)")
PY
  echo "== supervised server-restart smoke (real gRPC fleet; SIGKILL the server child mid-campaign under --supervise; run completes, fed_server_restarts_total == 1, report renders restarts) =="
  # server crash tolerance end-to-end (docs/ROBUSTNESS.md §Server crash
  # recovery) on REAL processes: rank 0 runs as a supervised child
  # (--supervise publishes its pid at <ckpt_dir>/server.pid), we SIGKILL
  # it once a round has committed, the supervisor restarts it, recovery
  # replays checkpoint + WAL, the surviving client processes ride the
  # gRPC backoff + resume probe, and the campaign completes. The final
  # telemetry close must export fed_server_restarts_total == 1 and the
  # post-restart round records must render a `restarts` column.
  SUP_DIR=./tmp/ci_supervise; rm -rf "$SUP_DIR"; mkdir -p "$SUP_DIR"
  SUP_WORLD=3; SUP_PORT=50620
  SUP_ARGS="--world_size $SUP_WORLD --backend grpc --base_port $SUP_PORT \
    --dataset synthetic --model lr --client_num_in_total 2 \
    --comm_round 6 --batch_size 10 --lr 0.1 --frequency_of_the_test 1"
  python -m fedml_tpu.experiments.distributed_launch --rank 0 $SUP_ARGS \
    --round_timeout_s 30 --supervise 2 --ckpt_dir "$SUP_DIR/ckpt" \
    --telemetry-dir "$SUP_DIR/tel" > "$SUP_DIR/server.out" 2>&1 &
  SUP_PID=$!
  SUP_CLIENT_PIDS=""
  for r in $(seq 1 $((SUP_WORLD - 1))); do
    python -m fedml_tpu.experiments.distributed_launch --rank "$r" \
      $SUP_ARGS > "$SUP_DIR/client$r.out" 2>&1 &
    SUP_CLIENT_PIDS="$SUP_CLIENT_PIDS $!"
  done
  # wait until a round has COMMITTED (a checkpoint exists), then kill the
  # server child dead — no goodbyes, exactly what the WAL is for
  for i in $(seq 1 240); do
    if [ -e "$SUP_DIR/ckpt/server.pid" ] \
        && ls "$SUP_DIR"/ckpt/round_* >/dev/null 2>&1; then break; fi
    sleep 0.5
  done
  ls "$SUP_DIR"/ckpt/round_* >/dev/null  # fail loudly if never committed
  kill -9 "$(cat "$SUP_DIR/ckpt/server.pid")"
  echo "-- SIGKILLed server child $(cat "$SUP_DIR/ckpt/server.pid"); waiting for the supervised campaign"
  wait $SUP_PID
  for p in $SUP_CLIENT_PIDS; do wait "$p"; done
  python - "$SUP_DIR" <<'PY'
import json, subprocess, sys

d = sys.argv[1]
recs = [json.loads(l) for l in open(f"{d}/tel/events.jsonl")]
rounds = [r for r in recs if r.get("kind") == "round"]
assert max(r["round"] for r in rounds) == 5, \
    f"campaign did not complete: {sorted(r['round'] for r in rounds)}"
assert any((r.get("server") or {}).get("restarts") == 1 for r in rounds), \
    "no post-restart round carries the server block"
prom = open(f"{d}/tel/metrics.prom").read()
line = [l for l in prom.splitlines()
        if l.startswith("fed_server_restarts_total")]
assert line and float(line[0].split()[-1]) == 1.0, line
table = subprocess.run(
    [sys.executable, "scripts/report.py", f"{d}/tel/events.jsonl"],
    capture_output=True, text=True, check=True).stdout
assert "restarts" in table, table[:400]
print(f"supervised server-restart smoke ok: {len(rounds)} round records "
      f"across the kill, fed_server_restarts_total == 1, restarts column "
      f"rendered")
PY
  echo "== fleet observability smoke (3-rank gRPC fleet under --supervise --fleet; mid-run /fleetz + fedtop --once; SIGKILL -> flight dumps + post-mortem timeline) =="
  # the fleet plane end-to-end on REAL processes (docs/OBSERVABILITY.md
  # §Fleet rollup / §Flight recorder & post-mortem): clients fold in-band
  # digests onto their uplinks (no client HTTP servers — --fleet without
  # --metrics_port on the client ranks), rank 0's /fleetz shows a row per
  # rank mid-run, fedtop --once renders the live rollup, then the server
  # child dies by SIGKILL under --supervise — the restarted child finishes
  # the campaign and report.py --post-mortem stitches WAL + per-rank
  # flight dumps into one timeline (restart epoch + starred pre-crash
  # client events)
  FLEET_DIR=./tmp/ci_fleet; rm -rf "$FLEET_DIR"; mkdir -p "$FLEET_DIR"
  FLEET_WORLD=3; FLEET_PORT=50640; FLEET_HTTP=50680
  # seeded straggle on the client uplinks pins the round cadence at >= 1s:
  # with a warm compile cache the whole campaign otherwise finishes before
  # the mid-run scrape window opens (no spaces in the JSON — FLEET_ARGS
  # expands unquoted)
  FLEET_CHAOS='{"seed":7,"rules":[{"fault":"straggle","src":[1,2],"dst":[0],"delay_s":1.0}]}'
  FLEET_ARGS="--world_size $FLEET_WORLD --backend grpc --base_port $FLEET_PORT \
    --dataset synthetic --model lr --client_num_in_total 2 \
    --comm_round 10 --batch_size 10 --lr 0.1 --frequency_of_the_test 1 \
    --chaos_plan $FLEET_CHAOS \
    --fleet 1 --fleet_job ci --telemetry-dir $FLEET_DIR/tel"
  python -m fedml_tpu.experiments.distributed_launch --rank 0 $FLEET_ARGS \
    --metrics_port $FLEET_HTTP --round_timeout_s 30 --supervise 2 \
    --ckpt_dir "$FLEET_DIR/ckpt" > "$FLEET_DIR/server.out" 2>&1 &
  FLEET_PID=$!
  FLEET_CLIENT_PIDS=""
  for r in $(seq 1 $((FLEET_WORLD - 1))); do
    python -m fedml_tpu.experiments.distributed_launch --rank "$r" \
      $FLEET_ARGS > "$FLEET_DIR/client$r.out" 2>&1 &
    FLEET_CLIENT_PIDS="$FLEET_CLIENT_PIDS $!"
  done
  # mid-run: wait for every rank's /fleetz row AND a committed round, scrape
  # the rollup, prove fedtop --once against the live endpoint, then SIGKILL
  # the server child dead — no goodbyes, the flight recorder's moment
  python - "$FLEET_DIR" "$FLEET_HTTP" <<'PY'
import glob, json, os, signal, subprocess, sys, time, urllib.request

d, port = sys.argv[1], int(sys.argv[2])
url = f"http://127.0.0.1:{port}/fleetz"
fleetz = None
for _ in range(480):
    try:
        cand = json.loads(urllib.request.urlopen(url, timeout=2).read())
        rows = cand.get("ranks", {})
        # round >= 1 on every client row: a round-0 digest precedes the
        # first uplink byte accounting, so the bytes assertion below
        # would race it
        if (set(rows) >= {"0", "1", "2"}
                and all((rows[r].get("round") or 0) >= 1
                        for r in ("1", "2"))
                and glob.glob(os.path.join(d, "ckpt", "round_*"))
                and os.path.exists(os.path.join(d, "ckpt", "server.pid"))):
            fleetz = cand
            break
    except OSError:
        pass
    time.sleep(0.25)
assert fleetz, "/fleetz never showed all 3 rank rows before the deadline"
assert fleetz["status"] == "ok" and fleetz["run"], fleetz
assert fleetz["job"] == "ci", fleetz
clients = {r: row for r, row in fleetz["ranks"].items() if r != "0"}
assert all(row.get("bytes_uplink", 0) > 0 for row in clients.values()), clients
top = subprocess.run(
    [sys.executable, "scripts/fedtop.py", "--url", f"127.0.0.1:{port}",
     "--once"], capture_output=True, text=True)
assert top.returncode == 0, top.stderr[:400]
assert "run=" in top.stdout and "job=ci" in top.stdout, top.stdout[:400]
pid = int(open(os.path.join(d, "ckpt", "server.pid")).read())
os.kill(pid, signal.SIGKILL)
print(f"mid-run fleet ok: /fleetz rows {sorted(fleetz['ranks'])}, "
      f"fedtop --once rendered, SIGKILLed server child {pid}")
PY
  echo "-- waiting for the supervised fleet campaign to complete"
  wait $FLEET_PID
  for p in $FLEET_CLIENT_PIDS; do wait "$p"; done
  python - "$FLEET_DIR" <<'PY'
import glob, json, re, subprocess, sys

d = sys.argv[1]
recs = [json.loads(l) for l in open(f"{d}/tel/events.jsonl")]
rounds = [r for r in recs if r.get("kind") == "round"]
assert max(r["round"] for r in rounds) == 9, \
    f"campaign did not complete: {sorted(r['round'] for r in rounds)}"
dumps = {json.load(open(p))["rank"]
         for p in glob.glob(f"{d}/tel/flightrec/rank*.json")}
assert dumps >= {1, 2}, f"client ranks left no flight dumps: {sorted(dumps)}"
pm = subprocess.run(
    [sys.executable, "scripts/report.py", f"{d}/tel/events.jsonl",
     "--post-mortem", "--wal-dir", f"{d}/ckpt/wal"],
    capture_output=True, text=True, check=True).stdout
assert ">>> restart" in pm and "restart epoch 1" in pm, pm[:600]
assert re.search(r"\* flight:[12]\b", pm), \
    "no starred pre-crash client flight event:\n" + pm[:600]
print(f"fleet post-mortem ok: {len(rounds)} round records across the kill, "
      f"flight dumps from ranks {sorted(dumps)}, timeline rendered with "
      f"restart epoch + pre-crash client events")
PY
  echo "CI GREEN (smoke tier — run 'scripts/ci.sh full' for the whole gate)"
  exit 0
fi

echo "== unit + oracle suite =="
python -m pytest tests/ -q

echo "== standalone smoke matrix =="
for spec in "fedavg mnist lr" "fedopt femnist cnn" "fedprox cifar10 resnet56" \
            "fednova shakespeare rnn" "feddf mnist lr"; do
  set -- $spec
  echo "-- $1 / $2 / $3"
  python -m fedml_tpu.experiments.cli --algo "$1" --dataset "$2" --model "$3" \
    --client_num_in_total 4 --client_num_per_round 2 --comm_round 2 \
    --batch_size 8 --max_batches 2 --ci 1 --frequency_of_the_test 1
done

echo "== long-context smoke (fedavg_seq on a 4x2 mesh) =="
python -m fedml_tpu.experiments.cli --algo fedavg_seq --dataset fed_shakespeare \
  --client_num_in_total 8 --client_num_per_round 4 --comm_round 2 \
  --batch_size 4 --lr 0.3 --mesh 8 --seq_shards 2 --max_batches 2 \
  --frequency_of_the_test 1 --ci 1

echo "== equivalence gate via summary files (CI-script-fedavg.sh:42-58 analogue) =="
# The reference asserts, to 3 decimals read from wandb-summary.json, that
# FedAvg(full participation, full batch, E=1) and hierarchical FL reproduce
# the same training accuracy (CI-script-fedavg.sh:42-58). Same gate here,
# through the SUMMARY FILES the runs emit (not in-process state): flat
# FedAvg vs hierarchical(1 group x 1 group_round) — the EXACT form of the
# invariance (the reference's 2-group variant only agrees to 3 decimals
# once accuracy saturates; the multi-group/mesh oracles live in
# tests/test_algorithms.py) — on the LEAF synthetic dataset (natural
# per-client splits -> Train/Acc is the _local_test_on_all_clients
# aggregate).
EQ_DIR=./tmp/ci_eq; rm -rf "$EQ_DIR"
EQ_ARGS="--dataset synthetic --client_num_in_total 30 --client_num_per_round 30 \
  --epochs 1 --batch_size 10000 --lr 0.03 --frequency_of_the_test 100 \
  --run_dir $EQ_DIR"
python -m fedml_tpu.experiments.cli --algo fedavg --comm_round 4 \
  $EQ_ARGS --run_name flat
flat_acc=$(python -c "import json; print(json.load(open('$EQ_DIR/flat/wandb-summary.json'))['Train/Acc'])")
python -m fedml_tpu.experiments.cli --algo hierarchical --comm_round 4 \
  --group_num 1 --group_comm_round 1 $EQ_ARGS --run_name hier
# read the per-run file (the latest-run copy is best-effort by design —
# RunLogger.finish() tolerates a read-only parent — so the gate must not
# risk comparing flat against a stale latest-run copy); the layout itself
# is pinned by tests/test_infra.py::test_run_logger_wandb_summary
hier_acc=$(python -c "import json; print(json.load(open('$EQ_DIR/hier/wandb-summary.json'))['Train/Acc'])")
python - "$flat_acc" "$hier_acc" <<'PY'
import sys
flat, hier = round(float(sys.argv[1]), 3), round(float(sys.argv[2]), 3)
assert flat == hier, f"equivalence gate FAILED: flat Train/Acc {flat} != hierarchical {hier}"
print(f"equivalence gate ok: Train/Acc {flat} == {hier} (3 decimals, via summary files)")
PY

echo "== cross-process smoke (loopback launcher roles) =="
python - <<'PY'
from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.models.linear import LogisticRegression

data = synthetic_images(num_clients=4, image_shape=(6, 6, 1), num_classes=3,
                        samples_per_client=12, test_samples=24, seed=0)
agg = run_simulated(data, classification_task(LogisticRegression(num_classes=3)),
                    FedAvgConfig(comm_round=2, client_num_in_total=4,
                                 client_num_per_round=2, batch_size=6,
                                 frequency_of_the_test=1), job_id="ci-smoke")
assert agg.history, "no eval records"
print("cross-process smoke ok:", agg.history[-1])
PY

echo "== chaos soak (seeded fault-injection campaign, docs/ROBUSTNESS.md) =="
# every trial's plan derives from its seed; the script replays every 5th
# trial and fails unless ledger + final model reproduce exactly
python scripts/chaos_soak.py --trials 5 --rounds 3 --out ./tmp/chaos_soak.json
# model-space tier: wire faults + a sign-flip Byzantine client defended by
# krum; replays must also reproduce the quarantine ledger, and the summary
# carries the backdoor defense spot check (evaluate_backdoor)
python scripts/chaos_soak.py --trials 3 --rounds 3 \
  --adversary-plan '{"seed": 5, "rules": [{"attack": "sign_flip", "ranks": [1], "factor": 10.0}]}' \
  --out ./tmp/chaos_soak_byz.json
# buffered-async tier: the same seeded wire faults over the event-driven
# async server (K-arrival flushes, staleness discounts, buffer deadline);
# replays assert the fault ledger + completion (arrival order is
# thread-scheduled — the bit-for-bit async replay is tier-1's virtual clock)
python scripts/chaos_soak.py --trials 3 --rounds 3 --async-buffer-k 2 \
  --out ./tmp/chaos_soak_async.json
# wire-codec tier: the same seeded wire faults with clients uploading
# deadzoned-int8 deltas (error feedback on); replays must still reproduce
# ledger + final model bits — the codec layer is deterministic
python scripts/chaos_soak.py --trials 3 --rounds 3 --compression delta-int8 \
  --out ./tmp/chaos_soak_codec.json
# cross-tier robust tier (docs/ROBUSTNESS.md §Cross-tier robust gating):
# seeded wire faults over the 2-tier tree topology with a krum-defended
# sign-flip adversary — chaos lands on both tiers (a crashed edge rank
# exercises the edge_lost elastic path), replay spot-checks also compare
# a chaos-free tree run's quarantine ledger + model bits against its
# flat pairwise twin, and the summary carries per-tier fan-in stats
python scripts/chaos_soak.py --trials 3 --rounds 3 --world_size 7 --edges 2 \
  --adversary-plan '{"seed": 5, "rules": [{"attack": "sign_flip", "ranks": [1], "factor": 10.0}]}' \
  --out ./tmp/chaos_soak_edges.json
# hierarchical masked secure-aggregation tier (docs/ROBUSTNESS.md
# §Hierarchical secure aggregation): the same seeded wire faults over the
# 2-tier MASKED tree — in-block dropout heals via the edge-local reveal,
# a crashed edge sheds exactly its block, replays assert liveness, and
# the chaos-free spot check pins masked tree == masked flat bitwise
# (model bits AND quarantine ledger)
python scripts/chaos_soak.py --secagg --trials 3 --rounds 3 --world_size 7 \
  --edges 2 --out ./tmp/chaos_soak_secagg.json
# server-crash tier (docs/ROBUSTNESS.md §Server crash recovery): seeded
# rank-0 kills through checkpoint + WAL recovery — even trials between
# commits must land bitwise on an uninterrupted oracle (model AND
# quarantine ledger), odd trials mid-round must complete with every
# accepted-then-lost slot ledgered server_restart
python scripts/chaos_soak.py --server-crash --trials 4 --rounds 4 \
  --out ./tmp/chaos_soak_crash.json

echo "== fleet campaign smoke (committed production-shaped profiles under a diurnal churn trace over a 100k-virtual-client streamed population; exactly-once outage accounting + bitwise replay; gated via ci_campaign_gate.json; runstore-ingested) =="
# docs/ROBUSTNESS.md §Fleet campaigns & client churn: the maximal legal
# compositions, end to end. ci_sync_tree = 2 edges x 8 gRPC workers,
# robust gating (median + sanitize), one supervised mid-round server
# SIGKILL (ckpt+WAL recovery) and one edge crash inside the run, plus a
# bitwise replay leg — the gate pins exactly-once ledger accounting
# (server_restart == after_uploads; edge_lost == block x reprobe span,
# no duplicate (round, rank)), zero quorum false-positives from
# scheduled-offline ranks, and replay model/ledger equality. async_flat
# = buffered async x poly staleness x delta-int8 x RANK-level churn
# (scheduled-offline dispatch admission). Both scrape /healthz +
# /fleetz live mid-run.
python scripts/fleet_campaign.py --profile ci_sync_tree --profile async_flat \
  --out ./tmp/ci_campaign
python scripts/bench_gate.py ./tmp/ci_campaign/ci_sync_tree_summary.json \
  --gate scripts/ci_campaign_gate.json
python scripts/bench_gate.py ./tmp/ci_campaign/async_flat_summary.json \
  --gate scripts/ci_campaign_gate.json
# the longitudinal record: both summaries join the runstore index
python scripts/runstore.py --index ./tmp/ci_runstore_index.jsonl ingest \
  ./tmp/ci_campaign/ci_sync_tree_summary.json \
  ./tmp/ci_campaign/async_flat_summary.json
python - <<'PY'
# the fleet plane actually ran: fed_fleet_* families in both runs' prom
# exports, and the churn families (fed_ranks_scheduled_offline,
# fed_rounds_idle_total) in the rank-churned async run
tree = open("./tmp/ci_campaign/ci_sync_tree/a/metrics.prom").read()
flat = open("./tmp/ci_campaign/async_flat/a/metrics.prom").read()
for fam in ("fed_fleet_ranks_reporting", "fed_fleet_digests_total",
            "fed_fleet_round_max", "fed_ranks_alive"):
    assert fam in tree, f"{fam} missing from the tree campaign export"
    assert fam in flat, f"{fam} missing from the async campaign export"
for fam in ("fed_ranks_scheduled_offline", "fed_rounds_idle_total"):
    assert fam in flat, f"{fam} missing from the rank-churned async export"
assert "fed_server_restarts_total" in tree, \
    "supervised restart left no fed_server_restarts_total in the export"
print("fleet campaign smoke ok: fleet + churn families exported")
PY
echo "CI GREEN"
