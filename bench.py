"""Benchmark: FedAvg rounds/sec on FEMNIST-shaped workload (BASELINE.json).

Runs the flagship config — FedAvg-paper CNN, 3400 simulated clients, 10
sampled per round, batch 20, E=1 (benchmark/README.md:54 setting) — and
prints ONE JSON line (the last stdout line is the authoritative result).

``python bench.py`` measures in THIS process, on the TPU it finds: the
per-round dispatch path first, then the flagship scanned-block path; each
prints its own JSON line(s) and the block line is the last. Without a TPU it
exits non-zero and prints no result. ``bench.py --measure <leg>`` runs one
leg alone on whatever backend jax has. The FEDML_BENCH_* A/B legs in
``main`` run their ``--measure`` children with JAX_PLATFORMS=cpu: they
compare protocols, codecs and host memory, not device throughput.

vs_baseline: the reference publishes no throughput numbers
(BASELINE.json.published = {}); its round latency is bounded below by the
MPI manager's 0.3 s receive-poll sleep (mpi/com_manager.py:71-78), so we use
1/0.3 ≈ 3.33 rounds/sec as the reference ceiling for the ratio.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_BASELINE_ROUNDS_PER_SEC = 1.0 / 0.3  # MPI poll-loop lower bound, see docstring


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, "") or default))
    except ValueError:
        print(f"bench: ignoring non-integer {name}", file=sys.stderr)
        return default


# Analytic forward FLOPs/sample for the flagship CNNOriginalFedAvg
# (reference model shapes, cnn.py:26-163): two SAME 5x5 convs with 2x2
# pooling between, then 3136->512->62 dense. A training step is ~3x the
# forward (fwd + 2 bwd matmul passes) — the standard MFU accounting.
_CNN_FWD_FLOPS = 2 * (28 * 28 * 5 * 5 * 1 * 32        # conv1 @ 28x28
                      + 14 * 14 * 5 * 5 * 32 * 64     # conv2 @ 14x14
                      + 3136 * 512 + 512 * 62)        # dense head
# Peak dense-matmul throughput per chip, bf16, FLOPs/s (public figures:
# v2 45 TF, v3 123 TF, v4 275 TF, v5e 197 TF, v5p 459 TF, v6e 918 TF).
# MFU is quoted against bf16 peak even for f32 runs (XLA runs f32
# contractions through the MXU in multi-pass bf16), so the f32 number is
# conservative. More-specific keys first: next() takes the first substring
# hit, and "v5"/"v6" alone would shadow the lite/p variants.
_PEAK_BF16 = {"v5 lite": 1.97e14, "v5e": 1.97e14, "v5p": 4.59e14,
              "v6 lite": 9.18e14, "v6e": 9.18e14,
              "v4": 2.75e14, "v3": 1.23e14, "v2": 4.5e13}


def _mfu(samples_per_sec_per_chip: float, platform: str) -> float | None:
    if platform != "tpu":
        return None  # no meaningful peak to quote against off-TPU
    import jax

    kind = jax.devices()[0].device_kind.lower()
    peak = next((v for k, v in _PEAK_BF16.items() if k in kind), None)
    if peak is None:
        return None  # unknown generation: a guessed peak would misreport
    return samples_per_sec_per_chip * 3 * _CNN_FWD_FLOPS / peak


def _result(rounds_per_sec: float, mode: str, samples_per_sec: float,
            n_chips: int, platform: str) -> dict:
    rec = {
        "metric": "fedavg_femnist_rounds_per_sec",
        "value": round(rounds_per_sec, 3),
        "unit": "rounds/sec",
        "vs_baseline": round(rounds_per_sec / _BASELINE_ROUNDS_PER_SEC, 2),
        # "block" = flagship scanned-block path; "per_round" = cheap
        # measurement (per-round dispatch) — do NOT compare the two against
        # each other
        "mode": mode,
        "samples_per_sec_per_chip": round(samples_per_sec / max(n_chips, 1), 1),
        "n_chips": n_chips,
        "platform": platform,
    }
    mfu = _mfu(rec["samples_per_sec_per_chip"], platform)
    if mfu is not None:
        # model FLOPs utilization vs bf16 peak — tiny by construction: the
        # flagship model is a 1.66M-param CNN at bs=20 (a cross-DEVICE
        # federated workload is dispatch/HBM-bound, not MXU-bound)
        rec["mfu_vs_bf16_peak"] = round(mfu, 5)
    return rec


# --------------------------------------------------------------------- child

def _mark(t0: float, msg: str) -> None:
    """Phase mark on stderr: where the set-up time went (data gen, the
    329 MB park, compile and round dispatch have very different costs)."""
    print(f"bench[{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _stamped(rec: dict) -> dict:
    """Provenance header (obs/provenance.py) on every measured blob: git
    sha, jax/jaxlib versions, device kind+count, and the wall-clock date.
    ``stamp`` never overwrites, so re-stamping a blob is a no-op."""
    try:
        from fedml_tpu.obs.provenance import stamp
        stamp(rec, date=time.strftime("%Y-%m-%d"))
    except Exception:  # noqa: BLE001 — provenance must never sink a bench
        pass
    return rec


def _bench_telemetry(mode: str):
    """The measured-variant telemetry bundle for one leg, or None (the
    default: FedAvgAPI(telemetry=None) builds the identical round program
    and adds zero work)."""
    # FEDML_BENCH_TELEMETRY_DIR=<dir>: write the obs event log (per-round
    # records + Prometheus dump; scripts/report.py renders it). A measured
    # VARIANT, never the headline default: floating the round metrics for
    # the event log syncs per round, which the overlap-dependent paths pay
    # for. Off (the default) adds zero work — FedAvgAPI(telemetry=None)
    # builds the identical round program.
    tdir = os.environ.get("FEDML_BENCH_TELEMETRY_DIR")
    # FEDML_BENCH_TRACE_DIR=<dir>: also ship the stitched per-round
    # timeline (obs/tracing.py) — trace.json per mode, Perfetto-loadable —
    # so a chip run can decompose its rounds/sec figure into
    # pack/compute/eval wall-clock instead of quoting one opaque number.
    # Implies telemetry (the spans ride the same bundle); a measured
    # VARIANT like the event log, never the headline default.
    trdir = os.environ.get("FEDML_BENCH_TRACE_DIR")
    # FEDML_BENCH_METRICS_PORT=<port>: live /metrics + /healthz for the
    # measuring process (docs/OBSERVABILITY.md §Live endpoints) — watch a
    # long TPU bench instead of waiting for its one JSON line. 0 = an
    # ephemeral port (logged + in the run header). Implies telemetry
    # (same measured-variant caveat as the event log).
    mport = os.environ.get("FEDML_BENCH_METRICS_PORT")
    if not (tdir or trdir or mport is not None):
        return None
    from fedml_tpu.obs import Telemetry

    # per-mode subdirectory: per_round and block are two runs — sharing one
    # events.jsonl would interleave their round records (duplicate round
    # numbers, mixed span bases) and the second close() would clobber the
    # first's metrics.prom
    telemetry = Telemetry(
        log_dir=os.path.join(tdir or trdir, mode) if tdir or trdir else None,
        trace_dir=os.path.join(trdir, mode) if trdir else None,
        run_id=f"bench_{mode}",
        http_port=int(mport) if mport is not None else None)
    if telemetry.http_port is not None:
        print(f"bench: live endpoints on "
              f"http://127.0.0.1:{telemetry.http_port}/metrics",
              file=sys.stderr)
    return telemetry


def _measure(mode: str) -> None:
    """Build the flagship workload and time it; prints one JSON line."""
    telemetry = _bench_telemetry(mode)
    try:
        _measure_mode(mode, telemetry)
    finally:
        if telemetry is not None:
            telemetry.close()  # frees the live-endpoint port for the next leg


def _measure_mode(mode: str, telemetry) -> None:
    t0 = time.perf_counter()
    import jax

    _mark(t0, f"jax imported; backend={jax.default_backend()}")

    # persistent compile cache: repeat bench runs (and driver re-runs)
    # skip the expensive first compile when the program is unchanged;
    # shared setup with every other entry point so they HIT the same cache
    from fedml_tpu.utils.metrics import enable_compile_cache

    enable_compile_cache()

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.registry import load_dataset
    from fedml_tpu.models.cnn import CNNOriginalFedAvg

    platform = jax.default_backend()
    n_chips = jax.device_count()

    block = _env_int("FEDML_BENCH_BLOCK", 10)
    n_timed = _env_int("FEDML_BENCH_ROUNDS", 20)
    n_timed = max(block, (n_timed // block) * block)  # whole blocks only
    n_cheap = _env_int("FEDML_BENCH_ROUNDS_CHEAP", 8)
    # debug/test knobs — leave unset for the flagship measurement
    clients_per_round = _env_int("FEDML_BENCH_CLIENTS_PER_ROUND", 10)
    max_batches = _env_int("FEDML_BENCH_MAX_BATCHES", 28)

    # FEDML_BENCH_MESH=N: shard the flagship round over an N-way
    # ('clients',) mesh (psum aggregation on ICI) instead of single-chip
    # vmap — the multi-chip path the dryrun validates, measurable wherever
    # N devices exist. Default: single-device (1 real chip under the
    # driver). clients_per_round rounds UP to a mesh multiple (the engine
    # requires even shards); the JSON's samples_per_sec_per_chip stays
    # comparable because count scales with the extra clients.
    mesh = None
    mesh_n = _env_int("FEDML_BENCH_MESH", 1)
    if mesh_n > 1:
        if n_chips < mesh_n:
            print(f"bench: FEDML_BENCH_MESH={mesh_n} but only {n_chips} "
                  "devices; staying single-device", file=sys.stderr)
        else:
            import numpy as np
            from jax.sharding import Mesh

            mesh = Mesh(np.asarray(jax.devices()[:mesh_n]), ("clients",))
            n_chips = mesh_n
            if clients_per_round % mesh_n:
                clients_per_round = -(-clients_per_round // mesh_n) * mesh_n
                print(f"bench: clients_per_round rounded up to "
                      f"{clients_per_round} (multiple of mesh {mesh_n})",
                      file=sys.stderr)

    # FEMNIST-shaped: 3400 clients, ~110 samples each (lognormal sizes);
    # uint8 pixels -> 4x less host->device transfer, normalized on device
    data = load_dataset("femnist", seed=0, uint8_pixels=True)
    _mark(t0, f"dataset built ({data.train_x.nbytes / 1e6:.0f} MB train)")
    cfg = FedAvgConfig(
        comm_round=block + n_timed,
        client_num_in_total=3400,
        client_num_per_round=clients_per_round,
        epochs=1,
        batch_size=20,
        lr=0.1,
        frequency_of_the_test=10_000,  # pure training throughput
        max_batches=max_batches,  # 28 covers ~[22,550]-sample clients at bs=20
    )
    # FEDML_BENCH_BF16=1: the full mixed-precision policy (docs/
    # PERFORMANCE.md §Mixed precision) — bf16 activations on the MXU AND
    # cfg.precision='bf16' so the vmapped local fits run on bf16 casts of
    # the f32 masters; f32 default for exact reference-comparable numerics
    dtype = None
    if os.environ.get("FEDML_BENCH_BF16") == "1":
        import dataclasses as _dc

        import jax.numpy as jnp

        dtype = jnp.bfloat16
        cfg = _dc.replace(cfg, precision="bf16")
    task = classification_task(CNNOriginalFedAvg(only_digits=False, dtype=dtype))
    # block mode parks the whole train set in HBM (~330 MB uint8) so a round
    # ships only the shuffled index block (~KBs) and gathers on device.
    # per_round mode deliberately does NOT (device_data=False): it measures
    # the host-packed plane, which ships only the sampled clients' rows
    # (~4 MB/round).
    # donate: round programs write outputs into the incoming model buffers.
    # block mode: working-set park by default — each block uploads only the
    # rows its sampled clients touch (~tens of MB) instead of parking the
    # full train set (~330 MB) up front; FEDML_BENCH_FULL_PARK=1 restores
    # the whole-set park (the right call on a fast local link)
    working_set = os.environ.get("FEDML_BENCH_FULL_PARK") != "1"
    # FEDML_BENCH_BUCKET_B=1: bucketed dynamic batch depth — bit-exact,
    # skips padded no-op batch compute; a mid-timing bucket change costs a
    # recompile, so it is a measured VARIANT, not the headline default
    bucket = os.environ.get("FEDML_BENCH_BUCKET_B") == "1"
    # a trace-dir run changes the headline variant below (_bench_telemetry)
    trdir = os.environ.get("FEDML_BENCH_TRACE_DIR")
    api = FedAvgAPI(data, task, cfg, device_data=(mode == "block"),
                    donate=True, mesh=mesh,
                    block_working_set=(mode == "block" and working_set),
                    bucket_batches=bucket, telemetry=telemetry)
    _mark(t0, f"api built (device_data={mode == 'block'}, "
              f"working_set={mode == 'block' and working_set})")

    if mode == "per_round":
        # cheap path: ONE small per-round program, timed a handful of
        # times.
        # Compile/warm-up cost is measured SEPARATELY from the timed
        # rounds and reported as compile_seconds: the parallel AOT warm-up
        # (api.warmup — .lower().compile() through the persistent cache)
        # plus the first executed round.
        t_c = time.perf_counter()
        wrep = api.warmup()
        api.run_round(0)  # warm: fills the jit dispatch cache from disk
        jax.block_until_ready(api.net.params)
        compile_seconds = time.perf_counter() - t_c
        _mark(t0, f"per_round warmup done ({wrep['fresh_compiles']} fresh "
                  f"compiles, {wrep['cache_hits']} cache hits)")
        api.prefetch = 2  # pipelined variant: double-buffered prefetch

        def timed_rounds(start: int, n: int, pipelined: bool):
            """(seconds, samples) over n rounds from a synced start."""
            tm = time.perf_counter()
            if pipelined:
                out = api.run_pipelined(start, n)
                ns = sum(float(m["count"]) for _, m in out)
            else:
                ns = 0.0
                for r in range(start, start + n):
                    ns += float(api.run_round(r)["count"])
            jax.block_until_ready(api.net.params)
            return time.perf_counter() - tm, ns

        # FEDML_BENCH_PIPELINE=0|1 picks the HEADLINE variant (default 1:
        # prefetch + lagged drain); the blob always carries the measured
        # A/B pair when the round budget allows both. A trace-dir run
        # defaults to 0: the pipelined driver emits no per-round
        # distributed traces (rounds overlap), so the variant being traced
        # must be the synchronous one unless the env says otherwise.
        head_pipe = os.environ.get("FEDML_BENCH_PIPELINE",
                                   "0" if trdir else "1") != "0"
        r_next, head_n = 1, n_cheap
        if n_cheap > 2:
            # an early, coarser JSON line after 2 rounds: a run that is cut
            # has still printed a real number; the LAST line is the result
            dt, ns = timed_rounds(r_next, 2, head_pipe)
            r_next += 2
            head_n = n_cheap - 2
            early = _result(2 / dt, "per_round", ns / dt, n_chips, platform)
            early["pipeline"] = int(head_pipe)
            print(json.dumps(_stamped(early)), flush=True)
            _mark(t0, "early 2-round salvage line printed")
        dt, ns = timed_rounds(r_next, head_n, head_pipe)
        r_next += head_n
        rec = _result(head_n / dt, "per_round", ns / dt, n_chips, platform)
        rec["pipeline"] = int(head_pipe)
        rec["compile_seconds"] = round(compile_seconds, 2)
        side = {"value": rec["value"],
                "samples_per_sec_per_chip": rec["samples_per_sec_per_chip"]}
        ab = {("on" if head_pipe else "off"): side}
        if n_cheap >= 4:
            # the refined headline is already measured — print it BEFORE
            # spending budget on the A/B other half, so a timeout during
            # the alt rounds salvages the full-precision number instead of
            # falling back to the coarse 2-round line
            print(json.dumps(_stamped(rec)), flush=True)
            _mark(t0, f"{head_n}-round headline printed (A/B half next)")
            # the A/B other half — skipped on degraded budgets (a 1-core
            # CPU box can barely afford the headline rounds)
            alt_n = max(2, n_cheap // 2)
            dt2, ns2 = timed_rounds(r_next, alt_n, not head_pipe)
            alt = _result(alt_n / dt2, "per_round", ns2 / dt2, n_chips,
                          platform)
            ab["off" if head_pipe else "on"] = {
                "value": alt["value"],
                "samples_per_sec_per_chip": alt["samples_per_sec_per_chip"]}
            _mark(t0, f"pipeline A/B pair measured: {ab}")
        rec["pipeline_ab"] = ab
        _mark(t0, f"{head_n} timed rounds done")
        print(json.dumps(_stamped(rec)))
        return

    # flagship path: rounds run in fixed-size blocks; jit caches by shape so
    # ONE compiled lax.scan block executable serves the warmup and every
    # timed block — no per-round dispatch, no per-round transfer beyond the
    # index blocks. Compile cost (AOT block warm-up where the shapes are
    # known up front + park + first block) is reported as compile_seconds,
    # never inside the timed rounds.
    t_c = time.perf_counter()
    if not working_set:
        # full park: block shapes are static — AOT-compile the block fn
        # (working-set row counts are data-dependent; the first block
        # compiles that variant instead)
        wrep = api.warmup(block_rounds=block, per_round=False)
        _mark(t0, f"block AOT warmup done ({wrep['fresh_compiles']} fresh "
                  f"compiles, {wrep['cache_hits']} cache hits)")
    api.run_rounds(0, block)
    jax.block_until_ready(api.net.params)
    compile_seconds = time.perf_counter() - t_c
    _mark(t0, "block warmup (park + compile + first block) done")
    tm = time.perf_counter()
    n_samples = 0.0
    timed = n_timed
    for i, start in enumerate(range(block, block + n_timed, block)):
        ms = api.run_rounds(start, block)
        n_samples += float(ms["count"].sum())
        if i == 0 and n_timed > block:
            jax.block_until_ready(api.net.params)
            dt = time.perf_counter() - tm
            print(json.dumps(_stamped(_result(block / dt, "block", n_samples / dt,
                                              n_chips, platform))), flush=True)
            _mark(t0, "early 1-block salvage line printed")
            # restart the clock (same reason as the per_round salvage): the
            # final number must not include the salvage sync/print
            n_samples, tm, timed = 0.0, time.perf_counter(), n_timed - block
    jax.block_until_ready(api.net.params)
    dt = time.perf_counter() - tm
    _mark(t0, f"{timed} timed rounds done")
    rec = _result(timed / dt, "block", n_samples / dt, n_chips, platform)
    rec["compile_seconds"] = round(compile_seconds, 2)
    print(json.dumps(_stamped(rec)))


# -------------------------------------------------------------------- parent

def _run_child(args: list[str], env: dict, timeout: int) -> tuple[int, str]:
    """Run a time-boxed forced-CPU A/B child; returns (rc, stdout). Never
    raises. On timeout the child gets SIGTERM and 20 s to unwind before
    SIGKILL; what it already printed still reaches us through the pipe."""
    try:
        proc = subprocess.Popen(
            [sys.executable, "-u", *args], env=env,
            stdout=subprocess.PIPE, stderr=sys.stderr,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
    except Exception as e:  # noqa: BLE001 — orchestrator must not die
        print(f"bench: child {args} failed to launch ({e})", file=sys.stderr)
        return 1, ""
    try:
        out, _ = proc.communicate(timeout=timeout)
        return proc.returncode, (out or b"").decode("utf-8", "replace")
    except subprocess.TimeoutExpired:
        print(f"bench: child {args} timed out after {timeout}s; terminating",
              file=sys.stderr)
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        return 124, (out or b"").decode("utf-8", "replace")
    except Exception as e:  # noqa: BLE001
        print(f"bench: child {args} failed ({e})", file=sys.stderr)
        proc.kill()
        proc.communicate()  # reap; leave no zombie/open pipe behind
        return 1, ""


def _last_json_line(out: str) -> dict | None:
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _cpu_env(base) -> dict:
    """Env of a forced-CPU A/B child."""
    env = dict(base)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _measure_async() -> None:
    """FEDML_BENCH_ASYNC A/B (docs/ROBUSTNESS.md §Asynchronous buffered
    rounds): the loopback cross-process stack under a seeded 1-rank
    straggler plan, synchronous barrier vs buffered-async — same number of
    global updates, wall-clock compared. The straggler owns every sync
    round (PR 3's critical path); async keeps aggregating without it. The
    env var picks the HEADLINE leg (lenient 0|1 spelling like
    FEDML_BENCH_PIPELINE); both legs always ride the blob. Runs forced-CPU
    loopback — the measurement isolates the round-coordination protocol,
    not device throughput."""
    t0 = time.perf_counter()
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.models.linear import LogisticRegression

    rounds = _env_int("FEDML_BENCH_ASYNC_ROUNDS", 6)
    world = _env_int("FEDML_BENCH_ASYNC_WORLD", 4)
    delay_s = float(os.environ.get("FEDML_BENCH_ASYNC_STRAGGLE_S", "0.3"))
    data = synthetic_images(num_clients=8, image_shape=(8, 8, 1),
                            num_classes=4, samples_per_client=24,
                            test_samples=96, seed=3)
    task = classification_task(LogisticRegression(num_classes=4))
    cfg = FedAvgConfig(comm_round=rounds, client_num_in_total=8,
                       client_num_per_round=world - 1, batch_size=8, lr=0.1,
                       frequency_of_the_test=10_000, seed=0)
    plan = lambda: FaultPlan.from_json(  # noqa: E731 — rebuilt per leg
        {"seed": 11, "rules": [{"fault": "straggle", "src": [2], "dst": [0],
                                "delay_s": delay_s}]})
    run_simulated(data, task, cfg, job_id="bench-async-warm")  # compile leg
    _mark(t0, "async A/B warm run done")

    def leg(async_mode: bool) -> dict:
        tl = time.perf_counter()
        agg = run_simulated(
            data, task, cfg, job_id=f"bench-async-{int(async_mode)}",
            chaos_plan=plan(), round_timeout_s=10.0,
            **(dict(async_buffer_k=max(2, (world - 1) // 2),
                    staleness="poly:0.5") if async_mode else {}))
        dt = time.perf_counter() - tl
        if not agg.history or agg.history[-1]["round"] != rounds - 1:
            raise RuntimeError(
                f"async A/B leg(async={async_mode}) did not complete "
                f"{rounds} global updates: {agg.history[-1:]}")
        return {"seconds": round(dt, 3),
                "rounds_per_sec": round(rounds / dt, 3),
                "updates": rounds}

    ab = {"off": leg(False), "on": leg(True)}
    _mark(t0, f"async A/B measured: {ab}")
    head = "on" if os.environ.get("FEDML_BENCH_ASYNC", "1") != "0" else "off"
    rec = {
        "metric": "fedavg_async_buffered_rounds_per_sec",
        "value": ab[head]["rounds_per_sec"],
        "unit": "rounds/sec",
        "mode": f"async_ab_{head}",
        "async_ab": ab,
        "straggle_s": delay_s,
        "rounds": rounds,
        "world_size": world,
        "speedup_async_vs_sync": round(
            ab["off"]["seconds"] / max(ab["on"]["seconds"], 1e-9), 2),
        "platform": "cpu",
    }
    print(json.dumps(_stamped(rec)), flush=True)


def _measure_dp() -> None:
    """FEDML_BENCH_DP ε-vs-accuracy A/B (docs/ROBUSTNESS.md §Privacy
    ledger): the masked secure-aggregation tier (distributed/
    turboaggregate.py) run once without DP and once per noise multiplier
    at MATCHED rounds and seed — per leg the final eval plus the privacy
    ledger's cumulative ε@δ (the round records carry the same block the
    blob summarizes). The blob is the privacy-cost evidence the CI gate
    (scripts/ci_dp_gate.json) pins: ε must fall as z rises, and the
    accuracy cost at the working point must stay bounded. Runs forced-CPU
    loopback — the measurement isolates the DP mechanism, not device
    throughput."""
    t0 = time.perf_counter()
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.distributed import turboaggregate as ta
    from fedml_tpu.models.linear import LogisticRegression

    rounds = _env_int("FEDML_BENCH_DP_ROUNDS", 8)
    world = _env_int("FEDML_BENCH_DP_WORLD", 9)
    clip = float(os.environ.get("FEDML_BENCH_DP_CLIP", "0.5"))
    data = synthetic_images(num_clients=32, image_shape=(8, 8, 1),
                            num_classes=4, samples_per_client=24,
                            test_samples=128, seed=3)
    task = classification_task(LogisticRegression(num_classes=4))
    cfg = FedAvgConfig(comm_round=rounds, client_num_in_total=32,
                       client_num_per_round=world - 1, epochs=1,
                       batch_size=8, lr=0.1, frequency_of_the_test=1,
                       seed=0)

    def leg(name: str, **kw) -> dict:
        agg = ta.run_simulated(data, task, cfg, job_id=f"bench-dp-{name}",
                               **kw)
        if not agg.history or agg.history[-1]["round"] != rounds - 1:
            raise RuntimeError(f"dp A/B leg {name} did not complete "
                               f"{rounds} rounds: {agg.history[-1:]}")
        rec = {"final_acc": round(agg.history[-1]["test_acc"], 4),
               "final_loss": round(agg.history[-1]["test_loss"], 4)}
        block = agg.privacy_record()
        if block:
            rec.update(eps=block["eps"], delta=block["delta"],
                       z=block["z"], clip=block["clip"], q=block["q"])
        return rec

    legs = {"plain": leg("plain")}
    for z in (0.6, 1.2):
        legs[f"z{z:g}"] = leg(
            f"z{z:g}", defense_type="dp", noise_multiplier=z,
            norm_bound=clip)
    _mark(t0, f"dp A/B measured: {legs}")
    rec = {
        "metric": "fedavg_dp_epsilon_at_z1.2",
        "value": legs["z1.2"]["eps"],
        "unit": "epsilon",
        "mode": "dp_ab",
        "dp_ab": legs,
        "rounds": rounds,
        "world_size": world,
        "clip": clip,
        # ε must FALL as z rises (the accountant's basic monotonicity,
        # gated), and the working point's accuracy cost stays bounded
        "eps_ratio_z0.6_over_z1.2": round(
            legs["z0.6"]["eps"] / max(legs["z1.2"]["eps"], 1e-9), 3),
        "dp_acc_drop_at_z0.6": round(
            legs["plain"]["final_acc"] - legs["z0.6"]["final_acc"], 4),
        "platform": "cpu",
    }
    print(json.dumps(_stamped(rec)), flush=True)


def _measure_codec() -> None:
    """FEDML_BENCH_CODEC A/B (docs/PERFORMANCE.md §Wire efficiency): the
    loopback cross-process stack run once per uplink codec tier — dense
    f32, lossless round-delta, deadzoned int8 delta, 1-bit scaled sign,
    top-k — at MATCHED round count and seed, measuring actual wire bytes
    per direction (``comm_bytes_total{codec,direction}`` deltas around
    each leg) against each tier's convergence curve. The blob is the
    bytes-vs-convergence evidence: per tier, uplink/downlink bytes,
    bytes/round, reduction vs dense, per-round losses, final eval. Runs
    forced-CPU loopback — the measurement isolates wire bytes and codec
    math, not device throughput."""
    t0 = time.perf_counter()
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs.comm_instrument import directional_bytes

    rounds = _env_int("FEDML_BENCH_CODEC_ROUNDS", 10)
    world = _env_int("FEDML_BENCH_CODEC_WORLD", 5)
    # ~16k params: big enough that frame headers don't dilute the byte
    # ratios (the regime the tiers target is models >> headers)
    data = synthetic_images(num_clients=8, image_shape=(40, 40, 1),
                            num_classes=10, samples_per_client=24,
                            test_samples=96, seed=3)
    task = classification_task(LogisticRegression(num_classes=10))
    cfg = FedAvgConfig(comm_round=rounds, client_num_in_total=8,
                       client_num_per_round=world - 1, epochs=1,
                       batch_size=8, lr=0.05, frequency_of_the_test=1,
                       seed=0)

    tiers = {
        "dense": {},
        "delta": {"update_codec": "delta"},
        "delta-int8": {"update_codec": "delta-int8"},
        "delta-sign1": {"update_codec": "delta-sign1"},
        "topk0.1": {"sparsify_ratio": 0.1},
    }
    out: dict = {}
    for name, kw in tiers.items():
        before = directional_bytes()
        tl = time.perf_counter()
        agg = run_simulated(data, task, cfg, job_id=f"bench-codec-{name}",
                            **kw)
        after = directional_bytes()
        if not agg.history or agg.history[-1]["round"] != rounds - 1:
            raise RuntimeError(f"codec leg {name} did not complete "
                               f"{rounds} rounds: {agg.history[-1:]}")
        up = after["uplink"] - before["uplink"]
        out[name] = {
            "uplink_bytes": int(up),
            "downlink_bytes": int(after["downlink"] - before["downlink"]),
            "uplink_bytes_per_round": round(up / rounds, 1),
            "losses": [round(float(h["test_loss"]), 6)
                       for h in agg.history],
            "final_loss": round(float(agg.history[-1]["test_loss"]), 6),
            "final_acc": round(float(agg.history[-1]["test_acc"]), 4),
            "seconds": round(time.perf_counter() - tl, 2),
        }
        _mark(t0, f"codec leg {name}: {out[name]['uplink_bytes']} uplink B, "
                  f"final loss {out[name]['final_loss']}")
    dense_up = out["dense"]["uplink_bytes"]
    for name, rec in out.items():
        rec["uplink_reduction_vs_dense"] = round(
            dense_up / max(rec["uplink_bytes"], 1), 2)
    rec = {
        "metric": "fedavg_uplink_reduction_int8_delta",
        "value": out["delta-int8"]["uplink_reduction_vs_dense"],
        "unit": "x_vs_dense_f32",
        "mode": "codec_ab",
        "rounds": rounds,
        "world_size": world,
        "uplink_reduction_sign1": out["delta-sign1"]
        ["uplink_reduction_vs_dense"],
        "tiers": out,
        "platform": "cpu",
    }
    print(json.dumps(_stamped(rec)), flush=True)


def _measure_fused_agg() -> None:
    """FEDML_BENCH_FUSED fused-vs-stacked server flush A/B (docs/
    PERFORMANCE.md §Fused aggregation): synthesize one cohort of
    delta-int8 uploads at fan-in FEDML_BENCH_FUSED_FANIN (default 128) and
    drive the two server ingest+aggregate routes at matched bits — the
    stacked route host-densifies every upload (zlib + numpy + apply_delta)
    and stacks the cohort, the fused route inflates to int8 and lets the
    per-arrival jit decode/gate/fold on device. Two timed phases per
    round, both synced: INGEST (per-arrival work — overlaps client
    training in production) and FLUSH (barrier -> new global model, the
    serialized critical path and the Smart-NIC seconds-per-flush number:
    stacked pays the [K, ...] stack + gagg jit there, fused only merges
    O(log K) partials and divides). Also reports the whole-server-round
    ratio (conservative) and the host-RSS delta across the ingest (the
    per-client f32 trees are exactly what fused never allocates).
    Forced-CPU child — the measurement isolates the server's decode→sum
    chain, not accelerator FLOPs."""
    t0 = time.perf_counter()
    import jax
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.comm.delta import (encode_update, inflate_update,
                                      round_delta, decode_update,
                                      apply_delta)
    from fedml_tpu.comm.message import pack_pytree
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.distributed.fedavg.aggregator import FedAvgAggregator
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs.memwatch import host_rss_bytes

    fan_in = _env_int("FEDML_BENCH_FUSED_FANIN", 128)
    rounds = _env_int("FEDML_BENCH_FUSED_ROUNDS", 5)
    # ~92k params (96x96 image -> 10 classes): big enough that the
    # per-upload decode/stack cost dominates the fixed jit dispatch
    data = synthetic_images(num_clients=8, image_shape=(96, 96, 1),
                            num_classes=10, samples_per_client=4,
                            test_samples=8, seed=3)
    task = classification_task(LogisticRegression(num_classes=10))
    cfg = FedAvgConfig(comm_round=rounds, client_num_in_total=fan_in,
                       client_num_per_round=fan_in, batch_size=4,
                       frequency_of_the_test=10_000, seed=0)
    _mark(t0, f"fused A/B workload built (fan-in {fan_in})")

    def synth_uploads(net_leaves, seed):
        """One cohort's encoded delta-int8 uploads (client work — never
        inside the flush timer)."""
        rs = np.random.RandomState(seed)
        out = []
        for _ in range(fan_in):
            local = [v + rs.randn(*np.shape(v)).astype(np.float32) * 0.01
                     for v in net_leaves]
            out.append(encode_update(round_delta(local, net_leaves),
                                     "delta-int8"))
        return out

    def leg(fused: bool, estimator: str | None = None) -> dict:
        # the robust leg (PR-21): estimator legs run the two-phase verdict
        # composition — stacked stages then runs the one-jit evidence →
        # verdicts → survivor fold over the [K, ...] stack; fused emits
        # per-arrival evidence rows and flushes the staged slots through
        # the identical shared composition (robust_agg.verdict_flush)
        agg = FedAvgAggregator(data, task, cfg, worker_num=fan_in,
                               fused_agg=fused, aggregator=estimator,
                               sum_assoc="auto" if fused else "pairwise")
        flush_s, ingest_s, rss_deltas = [], [], []
        for r in range(rounds + 1):  # round 0 = warm (jit compiles)
            agg.begin_round(r)
            base = [np.asarray(v) for v in pack_pytree(agg.net)]
            base_dev = [jax.device_put(v) for v in base] if fused else None
            uploads = synth_uploads(base, seed=100 + r)
            rss0 = host_rss_bytes() or 0
            # INGEST phase: per-arrival work — in production this runs
            # under the receive path while OTHER clients still train, so
            # it is off the barrier's critical path at realistic arrival
            # spreads; timed per cohort (synced) for the A/B anyway
            tl = time.perf_counter()
            for rank, (payload, scales) in enumerate(uploads):
                if fused:
                    raw, sc = inflate_update(payload, scales, "delta-int8",
                                             base)
                    agg.add_fused_result(rank, "delta-int8", raw, sc,
                                         10, r, base_dev)
                else:
                    dec = decode_update(payload, scales, "delta-int8", base)
                    agg.add_local_trained_result(
                        rank, apply_delta(base, dec), 10, r)
            if fused:
                agg._fused.block_until_ready()
            else:
                jax.block_until_ready(
                    [v for leaves in agg.model_dict.values()
                     for v in leaves if isinstance(v, jax.Array)])
            t_ing = time.perf_counter() - tl
            rss1 = host_rss_bytes() or 0
            # FLUSH phase: barrier -> new global model. ALWAYS serialized
            # on the round's critical path — this is the Smart-NIC
            # seconds-per-flush number. Stacked pays the [K, ...] stack +
            # gagg here; fused only merges O(log K) partials + divides.
            tl = time.perf_counter()
            agg._aggregate_core()
            jax.block_until_ready(jax.tree.leaves(agg.net))
            t_fl = time.perf_counter() - tl
            if r > 0:
                ingest_s.append(t_ing)
                flush_s.append(t_fl)
                rss_deltas.append(rss1 - rss0)
        return {"seconds_per_flush": round(float(np.mean(flush_s)), 4),
                "flush_s": [round(float(s), 4) for s in flush_s],
                "ingest_seconds_per_cohort":
                    round(float(np.mean(ingest_s)), 4),
                "server_seconds_per_round": round(
                    float(np.mean(ingest_s) + np.mean(flush_s)), 4),
                "ingest_rss_delta_bytes": int(np.max(rss_deltas)),
                "rss_end_bytes": int(host_rss_bytes() or 0),
                "stack_bytes": int(agg._last_flush["stack_bytes"]),
                "fan_in": fan_in}

    stacked = leg(False)
    _mark(t0, f"stacked leg: {stacked['seconds_per_flush']}s/flush + "
              f"{stacked['ingest_seconds_per_cohort']}s ingest")
    fused = leg(True)
    _mark(t0, f"fused leg: {fused['seconds_per_flush']}s/flush + "
              f"{fused['ingest_seconds_per_cohort']}s ingest")
    stacked_med = leg(False, estimator="median")
    _mark(t0, f"stacked median leg: "
              f"{stacked_med['seconds_per_flush']}s/flush")
    fused_med = leg(True, estimator="median")
    _mark(t0, f"fused median leg: {fused_med['seconds_per_flush']}s/flush")
    rec = {
        "metric": "fedavg_fused_flush_speedup",
        "value": round(stacked["seconds_per_flush"]
                       / max(fused["seconds_per_flush"], 1e-9), 2),
        "unit": "x_stacked_flush_over_fused",
        "mode": "fused_ab",
        "fused_ab": {"stacked": stacked, "fused": fused},
        "fused_flush_speedup": round(
            stacked["seconds_per_flush"]
            / max(fused["seconds_per_flush"], 1e-9), 2),
        # whole-server-round ratio (ingest + flush, both synced): the
        # conservative number — ingest normally overlaps client training
        "fused_server_round_speedup": round(
            stacked["server_seconds_per_round"]
            / max(fused["server_seconds_per_round"], 1e-9), 2),
        # the robust A/B (PR-21 universal ingest): fused×median's staged
        # flush vs stacked×median's verdict flush at the same fan-in
        "fused_robust_ab": {"stacked_median": stacked_med,
                            "fused_median": fused_med},
        "fused_robust_flush_speedup": round(
            stacked_med["seconds_per_flush"]
            / max(fused_med["seconds_per_flush"], 1e-9), 2),
        "fused_robust_server_round_speedup": round(
            stacked_med["server_seconds_per_round"]
            / max(fused_med["server_seconds_per_round"], 1e-9), 2),
        "fused_ingest_rss_delta_bytes": fused["ingest_rss_delta_bytes"],
        "stacked_ingest_rss_delta_bytes": stacked["ingest_rss_delta_bytes"],
        "fused_stack_bytes": fused["stack_bytes"],
        "stacked_stack_bytes": stacked["stack_bytes"],
        "fan_in": fan_in,
        "rounds": rounds,
        "platform": "cpu",
    }
    print(json.dumps(_stamped(rec)), flush=True)


def _bf16_dataset_dir() -> tuple[str, int]:
    """Size-skewed packed population for the bf16+bucket A/B: the static
    batch budget is priced by a 480-row tail client (0.1% of the
    population) while typical cohorts need a fraction of it — the
    FEMNIST-lognormal shape the bucket ladder exists for."""
    from fedml_tpu.core.client_source import PackedNpySource
    from fedml_tpu.data.synthetic import synthetic_packed_population

    n = _env_int("FEDML_BENCH_BF16_CLIENTS", 100_000)
    dim = _env_int("FEDML_BENCH_BF16_DIM", 32)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tmp",
                     f"bench_bf16_{n}x{dim}")
    if not os.path.isfile(os.path.join(d, "meta.json")):
        # 0.1% of clients at 480 rows, the rest 6-25: the static budget is
        # 60 batches while a typical 16-client cohort needs <= 4 — REAL
        # natural-partition shape (FEMNIST's lognormal max is ~20x its
        # p50), and exactly the regime the bucket ladder targets
        synthetic_packed_population(d, n, dim=dim, tail_size=480,
                                    tail_every=1000)
        PackedNpySource(d).close()
    return d, n


def _measure_bf16(leg: str) -> None:
    """One FEDML_BENCH_FUSED bf16 A/B leg in its own process: ``f32`` is
    the pre-policy engine (f32 compute, static batch budget every round),
    ``bf16`` the bf16+bucketed-vmap path (bf16 casts in the vmapped fits,
    per-cohort ladder depth). Matched rounds/seed/cohort over the same
    100k-client streamed population; reports rounds/s."""
    import dataclasses as _dc

    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.client_source import PackedNpySource
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.models.linear import LogisticRegression

    t0 = time.perf_counter()
    d, n = _bf16_dataset_dir()
    rounds = _env_int("FEDML_BENCH_BF16_ROUNDS", 42)
    src = PackedNpySource(d)
    cfg = FedAvgConfig(comm_round=rounds, client_num_in_total=n,
                       client_num_per_round=16, batch_size=8, lr=0.1,
                       epochs=_env_int("FEDML_BENCH_BF16_EPOCHS", 6),
                       frequency_of_the_test=10_000, seed=0)
    if leg == "bf16":
        cfg = _dc.replace(cfg, precision="bf16")
    task = classification_task(LogisticRegression(num_classes=5))
    api = FedAvgAPI(src, task, cfg, bucket_batches=(leg == "bf16"))
    api.warmup()
    api.run_round(0)
    api.run_round(1)
    _mark(t0, f"bf16 A/B leg {leg}: warm (2 rounds)")
    tl = time.perf_counter()
    for r in range(2, rounds):
        api.run_round(r)
    jax.block_until_ready(jax.tree.leaves(api.net.params))
    dt = time.perf_counter() - tl
    src.close()
    rec = {
        "leg": leg, "clients": n, "rounds": rounds,
        "bucketed": leg == "bf16",
        "seconds": round(dt, 3),
        "rounds_per_sec": round((rounds - 2) / dt, 3),
    }
    print(json.dumps(_stamped(rec)), flush=True)


def _stream_dataset_dir() -> tuple[str, int]:
    """Deterministic packed-npy population under ./tmp (built once,
    reused by both A/B legs so they read identical bytes) — the ONE
    shared fixture writer (data/synthetic.synthetic_packed_population),
    so this and the ci.sh flat-memory smoke cannot drift."""
    from fedml_tpu.core.client_source import PackedNpySource
    from fedml_tpu.data.synthetic import synthetic_packed_population

    n = _env_int("FEDML_BENCH_STREAM_CLIENTS", 100_000)
    dim = _env_int("FEDML_BENCH_STREAM_DIM", 16)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tmp",
                     f"bench_stream_{n}x{dim}")
    if not os.path.isfile(os.path.join(d, "meta.json")):
        synthetic_packed_population(d, n, dim=dim)
        PackedNpySource(d).close()  # smoke the layout before the legs run
    return d, n


def _measure_stream(leg: str) -> None:
    """One FEDML_BENCH_STREAM A/B leg in its own process (RSS is a
    process-level number — sharing a process would contaminate it):
    ``streamed`` runs the engine over the PackedNpySource (only the
    sampled cohort's rows ever reach memory), ``materialized`` loads the
    same population into a full FederatedData first (the pre-PR data
    plane). Matched rounds/seed/cohort; reports end RSS, across-round RSS
    growth, pack seconds, rounds/s."""
    import jax
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.client_source import PackedNpySource
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs.memwatch import host_rss_bytes

    t0 = time.perf_counter()
    d, n = _stream_dataset_dir()
    rounds = _env_int("FEDML_BENCH_STREAM_ROUNDS", 12)
    if leg == "streamed":
        data = PackedNpySource(d)
    else:
        from fedml_tpu.core.client_data import FederatedData

        src = PackedNpySource(d)
        offsets = np.load(os.path.join(d, "offsets.npy"))
        data = FederatedData(
            train_x=np.load(os.path.join(d, "x.npy")),
            train_y=np.load(os.path.join(d, "y.npy")),
            test_x=src.test_x, test_y=src.test_y,
            train_idx_map={c: np.arange(offsets[c], offsets[c + 1])
                           for c in range(n)},
            test_idx_map=None, class_num=5)
        src.close()
    cfg = FedAvgConfig(comm_round=rounds, client_num_in_total=n,
                       client_num_per_round=16, batch_size=8, lr=0.1,
                       frequency_of_the_test=10_000, seed=0)
    task = classification_task(LogisticRegression(num_classes=5))
    api = FedAvgAPI(data, task, cfg, bucket_batches=True)
    api.warmup()  # every bucket variant AOT-compiled before measuring
    api.run_round(0)
    api.run_round(1)
    _mark(t0, f"stream leg {leg}: warm (2 rounds)")
    rss0 = host_rss_bytes() or 0
    tl = time.perf_counter()
    for r in range(2, rounds):
        api.run_round(r)
    jax.block_until_ready(jax.tree.leaves(api.net.params))
    dt = time.perf_counter() - tl
    rss1 = host_rss_bytes() or 0
    rec = {
        "leg": leg, "clients": n, "rounds": rounds,
        "rss_end_bytes": int(rss1),
        "rss_growth_bytes": int(rss1 - rss0),
        "rss_growth_ratio": round(rss1 / max(rss0, 1), 4),
        "pack_seconds": round(float(
            api.tracer.rounds[-1].get("pack", 0.0)), 3),
        "seconds": round(dt, 3),
        "rounds_per_sec": round((rounds - 2) / dt, 3),
    }
    print(json.dumps(_stamped(rec)), flush=True)


def main() -> None:
    here = os.path.abspath(__file__)
    if os.environ.get("FEDML_BENCH_FUSED") is not None or \
            os.environ.get("FEDML_BENCH_FUSED_AGG") is not None:
        # fused-aggregation + bf16 A/B pair (docs/PERFORMANCE.md §Fused
        # aggregation / §Mixed precision) -> the BENCH_FUSED blob. Either
        # env var (any value) TRIGGERS the full A/B: both halves' legs
        # always run and ride the blob, and the headline is the fused
        # flush speedup (a ratio has no single-leg form to pick).
        # Forced-CPU children: the flush A/B isolates the server's
        # decode→sum chain, the bf16 A/B runs one child per leg at
        # matched rounds.
        rc, out = _run_child([here, "--measure", "fused_agg"],
                             _cpu_env(os.environ),
                             _env_int("FEDML_BENCH_FUSED_TIMEOUT", 900))
        fused_rec = _last_json_line(out)
        if fused_rec is None:
            raise RuntimeError(f"bench: fused A/B child failed (rc={rc})")
        legs = {}
        for leg in ("f32", "bf16"):
            rc, out = _run_child([here, "--measure", f"bf16_{leg}"],
                                 _cpu_env(os.environ),
                                 _env_int("FEDML_BENCH_BF16_TIMEOUT", 900))
            rec = _last_json_line(out)
            if rec is None:
                raise RuntimeError(
                    f"bench: bf16 A/B {leg} child failed (rc={rc})")
            legs[leg] = rec
        speedup = round(legs["bf16"]["rounds_per_sec"]
                        / max(legs["f32"]["rounds_per_sec"], 1e-9), 2)
        fused_rec.update({
            "bf16_ab": legs,
            "bf16_rounds_per_sec_speedup": speedup,
            "bf16_clients": legs["bf16"]["clients"],
        })
        print(json.dumps(fused_rec))
        return
    if os.environ.get("FEDML_BENCH_STREAM") is not None:
        # streamed-vs-materialized data-plane A/B (docs/PERFORMANCE.md
        # §Streaming & cohort bucketing) — one forced-CPU child PER LEG
        # (RSS is process-level; a shared process would contaminate it)
        legs = {}
        for leg in ("materialized", "streamed"):
            rc, out = _run_child([here, "--measure", f"stream_{leg}"],
                                 _cpu_env(os.environ),
                                 _env_int("FEDML_BENCH_STREAM_TIMEOUT",
                                          900))
            rec = _last_json_line(out)
            if rec is None:
                raise RuntimeError(
                    f"bench: stream A/B {leg} child failed (rc={rc})")
            legs[leg] = rec
        ratio = round(legs["streamed"]["rss_end_bytes"]
                      / max(legs["materialized"]["rss_end_bytes"], 1), 4)
        print(json.dumps({
            "metric": "fedavg_stream_rss_end_ratio",
            "value": ratio,
            "unit": "streamed_rss/materialized_rss",
            "mode": "stream_ab",
            "stream_ab": legs,
            "stream_clients": legs["streamed"]["clients"],
            "stream_rss_growth_bytes":
                legs["streamed"]["rss_growth_bytes"],
            "stream_rss_growth_ratio":
                legs["streamed"]["rss_growth_ratio"],
            "platform": "cpu",
        }))
        return
    if os.environ.get("FEDML_BENCH_CODEC") is not None:
        # wire-efficiency A/B — forced-CPU child (loopback threads; the
        # measurement is bytes-on-the-wire per codec tier, not FLOPs)
        rc, out = _run_child([here, "--measure", "codec"],
                             _cpu_env(os.environ),
                             _env_int("FEDML_BENCH_CODEC_TIMEOUT", 600))
        rec = _last_json_line(out)
        if rec is None:
            raise RuntimeError(f"bench: codec A/B child failed (rc={rc})")
        print(json.dumps(rec))
        return
    if os.environ.get("FEDML_BENCH_ASYNC") is not None:
        # protocol-level A/B — forced-CPU child (loopback threads; the
        # accelerator adds nothing to this measurement)
        rc, out = _run_child([here, "--measure", "async"],
                             _cpu_env(os.environ),
                             _env_int("FEDML_BENCH_ASYNC_TIMEOUT", 600))
        rec = _last_json_line(out)
        if rec is None:
            raise RuntimeError(f"bench: async A/B child failed (rc={rc})")
        print(json.dumps(rec))
        return
    if os.environ.get("FEDML_BENCH_DP") is not None:
        # ε-vs-accuracy A/B over the masked secure tier — forced-CPU
        # child (loopback threads; the DP mechanism is the measurement)
        rc, out = _run_child([here, "--measure", "dp"],
                             _cpu_env(os.environ),
                             _env_int("FEDML_BENCH_DP_TIMEOUT", 600))
        rec = _last_json_line(out)
        if rec is None:
            raise RuntimeError(f"bench: dp A/B child failed (rc={rc})")
        print(json.dumps(rec))
        return
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: needs a TPU, jax found {dev.platform!r} "
              f"({dev.device_kind}); no result", file=sys.stderr)
        sys.exit(1)
    # one process holds the chip: both legs run here, block line last
    _measure("per_round")
    _measure("block")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--measure":
        if sys.argv[2] == "async":
            _measure_async()
        elif sys.argv[2] == "codec":
            _measure_codec()
        elif sys.argv[2] == "dp":
            _measure_dp()
        elif sys.argv[2] == "fused_agg":
            _measure_fused_agg()
        elif sys.argv[2].startswith("bf16_"):
            _measure_bf16(sys.argv[2][len("bf16_"):])
        elif sys.argv[2].startswith("stream_"):
            _measure_stream(sys.argv[2][len("stream_"):])
        else:
            _measure(sys.argv[2])
    else:
        main()
