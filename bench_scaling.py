"""Client-scaling + cross-silo benchmarks: rounds/sec vs clients-per-round.

BASELINE.md north-star row 3: "client scaling 8 -> 256 simulated clients,
near-linear". The SPMD engine vmaps clients, so scaling K multiplies work
per round; throughput in samples/sec should grow until the chip saturates.

Workloads:
  - femnist_cnn (default): the flagship cross-device config (FedAvg CNN,
    28x28x1, 62 classes) — bench.py's workload at varying K.
  - cifar_resnet56: the reference's cross-silo setting (ResNet-56 on
    CIFAR-10 shapes, 10 clients, benchmark/README.md:105 — its RTX-2080Ti
    x4 distributed row) as one SPMD program on the chip.

Usage:  python bench_scaling.py [--workload cifar_resnet56] [--device_data 1]
                                [--points 8,32,128,256] [--spans 1]
Prints one JSON line per point (bench.py remains the single-line driver
benchmark; this script is the scaling study). A point that fails (e.g. out
of device memory) prints an error line and the sweep continues.
--spans 1 adds a host-side span breakdown (pack vs device compute vs eval,
obs/tracing.RoundTracer) to each point — where round time goes.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


_MEMO: dict = {}


def _one_point(args, data, task, k):
    import os

    import jax
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig

    cfg = FedAvgConfig(
        comm_round=args.rounds, client_num_in_total=data.num_clients,
        client_num_per_round=k, epochs=1, batch_size=args.batch_size, lr=0.1,
        frequency_of_the_test=10_000, max_batches=args.max_batches,
        remat=bool(args.remat),
    )
    # FEDML_BENCH_SHARDED_AGG=0|1 — the replicated-vs-sharded server-state
    # A/B (docs/PERFORMANCE.md §Partitioned server state): both legs run
    # the SAME mesh over every local device (so the comparison isolates
    # the server-plane layout, not mesh-vs-single-chip), 1 additionally
    # partitions the global model per the rule table. Unset = the
    # historical single-chip sweep, untouched.
    sharded_env = os.environ.get("FEDML_BENCH_SHARDED_AGG")
    mesh, shard = None, False
    if sharded_env is not None:
        ndev = jax.device_count()
        if ndev > 1 and k % ndev == 0:
            from jax.sharding import Mesh

            mesh = Mesh(np.array(jax.devices()), ("clients",))
            # same lenient spelling as bench.py's FEDML_BENCH_PIPELINE
            shard = sharded_env != "0"
        else:
            why = ("only one device visible" if ndev <= 1
                   else f"k={k} not a multiple of {ndev} devices")
            print(f"bench_scaling: FEDML_BENCH_SHARDED_AGG set but {why} "
                  "— point runs unmeshed", file=sys.stderr)
    api = FedAvgAPI(data, task, cfg, device_data=bool(args.device_data),
                    donate=True, mesh=mesh, shard_server_state=shard,
                    block_working_set=bool(args.device_data)
                    and bool(args.working_set))

    if args.device_data:
        # one compiled scan per block, no per-round host dispatch (bench.py
        # uses the same path). NOTE: with the working-set plane the timed
        # window deliberately includes each block's host-side row compaction
        # + upload — that IS the per-block cost of this plane; the span
        # breakdown separates it (host_pack). --working_set 0 (or
        # FEDML_BENCH_FULL_PARK=1) restores pure device throughput with the
        # whole train set parked before timing starts.
        api.run_rounds(0, args.rounds)
        jax.block_until_ready(api.net.params)
        base = api.tracer.totals()  # warmup holds the compile; exclude
        t0 = time.perf_counter()
        ms = api.run_rounds(args.rounds, args.rounds)
        jax.block_until_ready(api.net.params)
        count = float(ms["count"][-1])
    else:
        api.run_round(0)
        jax.block_until_ready(api.net.params)
        base = api.tracer.totals()
        t0 = time.perf_counter()
        for r in range(1, args.rounds + 1):
            m = api.run_round(r)
        jax.block_until_ready(api.net.params)
        count = float(m["count"])
    dt = time.perf_counter() - t0
    rps = args.rounds / dt
    rec = {
        "workload": args.workload,
        "clients_per_round": k,
        "rounds_per_sec": round(rps, 3),
        "samples_per_sec": round(count * rps, 1),
        "device": jax.devices()[0].platform,
        "data_plane": (("working_set" if api.block_working_set else "full_park")
                       if args.device_data else "host_pack"),
        "dtype": "bf16" if args.bf16 else "f32",
        "remat": bool(args.remat),
    }
    if mesh is not None:
        # per-device memory stats for the A/B blob: the rule-table figure
        # (exact, what fed_server_state_bytes exports) plus the backend's
        # live allocator view where it exists (TPU; CPU returns nothing)
        rec["server_state"] = {
            "mode": api._state_placement,
            "bytes_per_device": api._agg_record[
                "server_state_bytes_per_device"],
            "devices": int(np.prod(list(mesh.shape.values()))),
        }
        try:
            mstats = jax.devices()[0].memory_stats() or {}
            if "bytes_in_use" in mstats:
                rec["server_state"]["device0_bytes_in_use"] = int(
                    mstats["bytes_in_use"])
        except Exception:  # noqa: BLE001 — allocator stats are best-effort
            pass
    # MFU vs bf16 peak (TPU only): XLA's own FLOP count of the compiled
    # forward on one batch, 3x-forward train accounting (utils/flops.py).
    # Memoized: the forward is identical across every sweep point.
    import jax.numpy as jnp

    from fedml_tpu.utils.flops import compiled_flops, train_mfu

    if "fwd_flops" not in _MEMO:
        xb = jnp.asarray(data.train_x[: args.batch_size])
        _MEMO["fwd_flops"] = compiled_flops(api.task.predict, api.net.params,
                                            api.net.extra, xb)
    fwd = _MEMO["fwd_flops"]
    if fwd:
        mfu = train_mfu(count * rps, fwd / args.batch_size)
        if mfu is not None:
            rec["mfu_vs_bf16_peak"] = round(mfu, 5)
            rec["fwd_flops_per_sample"] = round(fwd / args.batch_size)
    if args.spans:
        # where TIMED-window wall-clock goes. Tracer spans give the host
        # side (index/data packing); everything else is the device program
        # + dispatch (the engines dispatch asynchronously, so per-span
        # device timing is not separable host-side — the residual is).
        # The warmup compile is excluded (delta vs the post-warmup base).
        end = api.tracer.totals()
        pack = end.get("pack", 0.0) - base.get("pack", 0.0)
        rec["span_seconds"] = {
            "host_pack": round(pack, 3),
            "device_plus_dispatch": round(max(0.0, dt - pack), 3),
        }
    try:
        # provenance header (obs/provenance.py): git sha, versions, device
        # kind/count, date — consumers tolerate absence on historical blobs
        from fedml_tpu.obs.provenance import stamp
        stamp(rec, date=time.strftime("%Y-%m-%d"))
    except Exception:  # noqa: BLE001 — provenance must never sink a point
        pass
    print(json.dumps(rec), flush=True)


def main():
    from fedml_tpu.utils.metrics import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", type=str, default="femnist_cnn",
                    choices=["femnist_cnn", "cifar_resnet56"])
    ap.add_argument("--points", type=str, default=None,
                    help="clients-per-round sweep; default 8,32,128,256 "
                         "(femnist_cnn) or 10 (cifar_resnet56 = the "
                         "reference cross-silo client count)")
    ap.add_argument("--device_data", type=int, default=1)
    ap.add_argument("--working_set", type=int, default=0,
                    help="with --device_data: per-block working-set park "
                         "(upload only the rows a block touches) instead "
                         "of parking the whole train set up front. Opt-in "
                         "(like the CLI's --working_set): it moves per-block "
                         "host compaction+upload INTO the timed window, so "
                         "sweep numbers are only comparable to other "
                         "working-set sweeps")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--batch_size", type=int, default=None)
    ap.add_argument("--max_batches", type=int, default=None)
    ap.add_argument("--spans", type=int, default=1)
    ap.add_argument("--samples_per_client", type=int, default=None)
    # HBM-pressure knobs for the cross-silo workload (the 10-client vmapped
    # ResNet-56 program): bf16 activations halve activation HBM; remat
    # (jax.checkpoint around the per-batch local update) trades FLOPs for
    # activation memory. Exercise on the real chip if the full-precision
    # program doesn't fit.
    ap.add_argument("--bf16", type=int, default=0)
    ap.add_argument("--remat", type=int, default=0)
    args = ap.parse_args()
    if args.device_data and args.working_set:
        print("bench_scaling: working-set plane ON — the timed window now "
              "includes per-block host compaction+upload; numbers are not "
              "comparable to full-park sweeps", file=sys.stderr)

    from fedml_tpu.core.tasks import classification_task

    dtype = None
    if args.bf16:
        import jax.numpy as jnp

        dtype = jnp.bfloat16
    if args.workload == "cifar_resnet56":
        from fedml_tpu.data.synthetic import synthetic_images
        from fedml_tpu.models.resnet import ResNetCIFAR

        args.points = args.points or "10"
        args.batch_size = args.batch_size or 64
        args.max_batches = args.max_batches or 8
        # 10 silos, CIFAR-10 shapes (benchmark/README.md:105 setting);
        # uint8 pixels like the flagship path
        data = synthetic_images(
            num_clients=10, image_shape=(32, 32, 3), num_classes=10,
            samples_per_client=args.samples_per_client or 512,
            test_samples=512, seed=0, size_lognormal=False, as_uint8=True)
        task = classification_task(ResNetCIFAR(depth=56, num_classes=10,
                                               norm_type="group", dtype=dtype))
    else:
        from fedml_tpu.data.registry import load_dataset
        from fedml_tpu.models.cnn import CNNOriginalFedAvg

        args.points = args.points or "8,32,128,256"
        args.batch_size = args.batch_size or 20
        args.max_batches = args.max_batches or 28
        data = load_dataset("femnist", seed=0, uint8_pixels=True)
        task = classification_task(CNNOriginalFedAvg(only_digits=False,
                                                     dtype=dtype))

    for k in [int(p) for p in args.points.split(",")]:
        try:
            _one_point(args, data, task, k)
        except Exception as e:  # noqa: BLE001 — later points still measured
            print(json.dumps({"clients_per_round": k,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)


if __name__ == "__main__":
    main()
