"""chip_smoke.py — the quickest proof that fedml_tpu still starts on the chip.

Drives the system's main paths once through the entry points a user calls,
in ONE process on ONE TPU chip, each model at its full width (depth and the
client population are cut; data is synthetic, made from --seed):

  femnist_cnn     CNNOriginalFedAvg/62 classes through the CLI, then the
                  scanned block driver (FedAvgAPI.run_rounds + warmup)
  cifar_resnet56  the cross-silo ResNet-56 (group norm), f32 and bf16
  flash_lm        TransformerLM on the Pallas flash kernel through FedAvgAPI,
                  and the kernel alone against full_attention
  server_ingest   the cross-process runtime on the loopback transport with
                  delta-int8 uplinks, stacked and fused aggregation

`--chips 4` runs only the client-mesh phase and its single-device twin.

Every phase checks finite parameters, a falling loss (or rising accuracy)
and that the global model lives on a TPU device; any failed check raises.
Without a TPU the script exits non-zero and prints no result line. The last
line of stdout is {"ok": true, "device": {...}} as jax reports the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from fedml_tpu import native
from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.client_data import batch_global
from fedml_tpu.core.tasks import classification_task, sequence_task
from fedml_tpu.data.registry import load_dataset
from fedml_tpu.data.synthetic import synthetic_images, synthetic_sequences
from fedml_tpu.distributed.fedavg import run_simulated
from fedml_tpu.experiments import cli
from fedml_tpu.models import create_model
from fedml_tpu.models.cnn import CNNOriginalFedAvg
from fedml_tpu.models.resnet import ResNetCIFAR
from fedml_tpu.obs import perf_instrument as perf
from fedml_tpu.obs.memwatch import device_memory_stats
from fedml_tpu.obs.metrics import REGISTRY
from fedml_tpu.ops import flash_attention
from fedml_tpu.parallel.ring_attention import full_attention
from fedml_tpu.utils.flops import bf16_peak
from fedml_tpu.utils.metrics import enable_compile_cache

# full-width sizes of each phase; the CPU rehearsal shrinks these from
# outside (a scratch script), never through an option of this script
FEMNIST = dict(clients=340, per_round=10, batch_size=20, max_batches=28,
               cli_rounds=4, block_rounds=5)
# lr: the reference's cross-silo ResNet-56 runs SGD at 0.001 (BASELINE.md);
# at the 0.1 of the cross-device cells the first local fit already diverges
RESNET = dict(silos=10, batch_size=64, max_batches=8, samples_per_client=512,
              rounds=2, lr=0.001)
FLASH_LM = dict(dim=512, heads=8, depth=2, seq_len=1024, vocab=256,
                clients=4, samples_per_client=4, batch_size=2, rounds=3)
# (B, T, H, D): heads cut so the dense f32 reference fits next to the kernel
FLASH_KERNEL_SHAPES = [(2, 2048, 8, 64), (1, 8192, 2, 128)]
FLASH_TOL = 2e-2  # max |kernel - reference| / max |reference|, bf16 outputs
INGEST = dict(workers=8, rounds=2, clients=16, batch_size=20, max_batches=6)
# fused vs stacked final weights. On the chip the two are bitwise twins
# (0.0, PR 22). On the CPU they are not: XLA:CPU contracts the device decode's
# scale*q + base into an fma, and delta-int8's re-quantisation of the next
# round carries that last bit across an int8 rounding boundary (5e-5).
INGEST_TOL = 1e-5
# the whole population takes part in every round, so the training loss of
# consecutive rounds is comparable. The phase runs at matmul precision
# 'highest': at the TPU's default (bf16 passes) a vmap of 8 clients and four
# vmaps of 2 round differently and the histories part by 4.5e-3 within two
# rounds. Even at 'highest' the chip's convolutions are not f32-exact (its
# round-0 losses differ from the CPU's by 4e-5), so the mesh agrees with the
# single device to 1e-4 over the first round (6.3e-5) and SGD on the CNN
# widens that about tenfold per round (6.7e-4 after two; on the CPU, f32
# exact, 5e-7 after two). All measured on four chips, PR 22.
MESH = dict(clients=8, per_round=8, batch_size=20, max_batches=8, rounds=2,
            lr=0.03)
MESH_FIRST_ROUND_TOL = 1e-4
MESH_TOL = 2e-3

def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def say(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}, default=float), flush=True)


def check_model(net, platform: str, what: str) -> None:
    """Finite parameters, resident on ``platform`` devices."""
    leaves = jax.tree.leaves(net)
    check(bool(leaves), f"{what}: empty model")
    for leaf in leaves:
        check(bool(jnp.all(jnp.isfinite(leaf))), f"{what}: non-finite leaf")
        plats = {d.platform for d in leaf.devices()}
        check(plats == {platform}, f"{what}: leaf on {plats}, not {platform}")


def _round_losses(ms) -> list[float]:
    """Per-round mean training loss from a stacked block-metrics dict."""
    loss = np.asarray(ms["loss_sum"], np.float64)
    return list(loss / np.maximum(np.asarray(ms["count"], np.float64), 1.0))


def _compile_counters() -> dict:
    return {"compiles": perf.compiles_total(),
            "cache_hits": perf.cache_hits_total(),
            "cache_misses": perf.cache_misses_total(),
            "compile_seconds": REGISTRY.total(
                "fed_xla_variant_compile_seconds_total")}


def _femnist_setup(seed: int, *, clients, per_round, rounds, batch_size,
                   max_batches, lr=0.1, eval_every=10_000):
    """(data, task, cfg) of the flagship at full width: the FEMNIST-shaped
    synthetic population, CNNOriginalFedAvg with 62 classes, uint8 pixels."""
    data = load_dataset("femnist", client_num=clients, seed=seed,
                        uint8_pixels=True)
    task = classification_task(CNNOriginalFedAvg(only_digits=False))
    cfg = FedAvgConfig(
        comm_round=rounds, client_num_in_total=clients,
        client_num_per_round=per_round, epochs=1, batch_size=batch_size,
        lr=lr, frequency_of_the_test=eval_every, max_batches=max_batches,
        seed=seed)
    return data, task, cfg


# ------------------------------------------------------------------ phases
def femnist_cnn(seed: int, platform: str, workdir: str) -> dict:
    s = FEMNIST
    # L5 entry point, per-round dispatch, eval every round
    cli.main(["--algo", "fedavg", "--model", "cnn", "--dataset", "femnist",
              "--client_num_in_total", str(s["clients"]),
              "--client_num_per_round", str(s["per_round"]),
              "--batch_size", str(s["batch_size"]),
              "--max_batches", str(s["max_batches"]), "--lr", "0.1",
              "--comm_round", str(s["cli_rounds"]),
              "--frequency_of_the_test", "1", "--uint8_pixels", "1",
              "--seed", str(seed), "--run_dir", workdir,
              "--run_name", "femnist_cli"])
    with open(os.path.join(workdir, "femnist_cli", "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    check(len(recs) == s["cli_rounds"], f"cli logged {len(recs)} rounds")
    check(recs[-1]["train_loss"] < recs[0]["train_loss"]
          or recs[-1]["test_acc"] > recs[0]["test_acc"],
          f"cli run did not learn: {recs[0]} -> {recs[-1]}")

    # block mode: the train set parked on the device, R rounds per dispatch
    data, task, cfg = _femnist_setup(
        seed, clients=s["clients"], per_round=s["per_round"],
        rounds=2 * s["block_rounds"], batch_size=s["batch_size"],
        max_batches=s["max_batches"])
    api = FedAvgAPI(data, task, cfg, device_data=True, donate=True)
    wrep = api.warmup(block_rounds=s["block_rounds"], per_round=False)
    losses = []
    for start in (0, s["block_rounds"]):
        losses += _round_losses(api.run_rounds(start, s["block_rounds"]))
    jax.block_until_ready(api.net.params)
    check(losses[-1] < losses[0], f"block losses did not fall: {losses}")
    check_model(api.net, platform, "femnist block")
    return {"cli_train_loss": [r["train_loss"] for r in recs],
            "cli_test_acc": [r["test_acc"] for r in recs],
            "block_losses": losses,
            "warmup_fresh_compiles": wrep["fresh_compiles"],
            "warmup_cache_hits": wrep["cache_hits"]}


def cifar_resnet56(seed: int, platform: str) -> dict:
    s = RESNET
    # the model, shapes and optimizer of benchmark/'s cifar_resnet56
    data = synthetic_images(
        num_clients=s["silos"], image_shape=(32, 32, 3), num_classes=10,
        samples_per_client=s["samples_per_client"], test_samples=512,
        seed=seed, size_lognormal=False, as_uint8=True)
    cfg = FedAvgConfig(
        comm_round=s["rounds"], client_num_in_total=s["silos"],
        client_num_per_round=s["silos"], epochs=1,
        batch_size=s["batch_size"], lr=s["lr"], frequency_of_the_test=10_000,
        max_batches=s["max_batches"], seed=seed)
    out = {}
    for name, dtype in (("f32", None), ("bf16", jnp.bfloat16)):
        task = classification_task(ResNetCIFAR(
            depth=56, num_classes=10, norm_type="group", dtype=dtype))
        api = FedAvgAPI(data, task, cfg, device_data=True, donate=True)
        t0 = time.perf_counter()
        losses = _round_losses(api.run_rounds(0, s["rounds"]))
        jax.block_until_ready(api.net.params)
        check(losses[-1] < losses[0], f"resnet56 {name} losses: {losses}")
        check_model(api.net, platform, f"resnet56 {name}")
        out[name] = {"losses": losses,
                     "seconds": time.perf_counter() - t0}
    return out


def _kernel_compiled(fn, *args):
    """``fn`` compiled for ``args``; fails unless the program holds a Mosaic
    kernel, so neither the interpreter nor the dense twin can stand in."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "compiled program holds no tpu_custom_call")
    return compiled


def flash_lm(seed: int, platform: str) -> dict:
    s = FLASH_LM
    model = create_model("transformer_flash", output_dim=s["vocab"],
                         dim=s["dim"], depth=s["depth"],
                         num_heads=s["heads"], max_len=s["seq_len"])
    data = synthetic_sequences(
        num_clients=s["clients"], seq_len=s["seq_len"], vocab_size=s["vocab"],
        samples_per_client=s["samples_per_client"],
        test_samples=s["batch_size"], seed=seed)
    cfg = FedAvgConfig(
        comm_round=s["rounds"], client_num_in_total=s["clients"],
        client_num_per_round=s["clients"], epochs=1,
        batch_size=s["batch_size"], lr=0.1, frequency_of_the_test=10_000,
        seed=seed)
    api = FedAvgAPI(data, sequence_task(model), cfg)
    tokens = jnp.asarray(data.train_x[: s["batch_size"]], jnp.int32)
    _kernel_compiled(lambda p, x: model.apply({"params": p}, x),
                     api.net.params, tokens)
    losses = []
    for r in range(s["rounds"]):
        m = api.run_round(r)
        losses.append(float(m["loss_sum"]) / max(float(m["count"]), 1.0))
    check(losses[-1] < losses[0], f"flash_lm losses did not fall: {losses}")
    check_model(api.net, platform, "flash_lm")

    # the kernel alone against the dense reference, forward and gradients
    errs = {}
    for shape in FLASH_KERNEL_SHAPES:
        kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, k, v = (jax.random.normal(key, shape, jnp.float32)
                   .astype(jnp.bfloat16) for key in (kq, kk, kv))
        w = jax.random.normal(kw, shape, jnp.float32)

        def flash_loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True)
                           .astype(jnp.float32) * w)

        def dense_loss(q, k, v):
            return jnp.sum(full_attention(q, k, v, causal=True) * w)

        out = _kernel_compiled(
            lambda q, k, v: flash_attention(q, k, v, True), q, k, v)(q, k, v)
        grads = _kernel_compiled(
            jax.grad(flash_loss, (0, 1, 2)), q, k, v)(q, k, v)
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, k, v: full_attention(
                q, k, v, causal=True))(*f32)
            ref_grads = jax.jit(jax.grad(dense_loss, (0, 1, 2)))(*f32)
        for name, a, b in zip(("out", "dq", "dk", "dv"),
                              (out, *grads), (ref, *ref_grads)):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b)
            check(bool(np.all(np.isfinite(a))), f"flash {shape} {name} nan")
            err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            errs[f"T{shape[1]}_D{shape[3]}_{name}"] = err
            check(err <= FLASH_TOL,
                  f"flash {shape} {name}: rel-to-max error {err} > {FLASH_TOL}")
    return {"losses": losses, "kernel_rel_err": errs, "tolerance": FLASH_TOL}


def server_ingest(seed: int, platform: str) -> dict:
    s = INGEST
    data, task, cfg = _femnist_setup(
        seed, clients=s["clients"], per_round=s["workers"],
        rounds=s["rounds"], batch_size=s["batch_size"],
        max_batches=s["max_batches"], eval_every=1)
    stacked = run_simulated(data, task, cfg, job_id="smoke-stacked",
                            update_codec="delta-int8", sum_assoc="pairwise")
    fused = run_simulated(data, task, cfg, job_id="smoke-fused",
                          update_codec="delta-int8", fused_agg=True)
    check(fused.fused_agg and fused.agg_record().get("fused") is True,
          "fused run did not take the fused ingest path")
    # the server evaluates on a uniform test set, flat over the first rounds
    # of non-IID clients; learning shows on the clients' own training rows
    train = [jnp.asarray(a) for a in
             batch_global(data.train_x, data.train_y, 256)]
    # the server's own starting weights (same key derivation, aggregator.py)
    _, init_key = jax.random.split(jax.random.PRNGKey(seed))
    fresh = task.init(init_key, jnp.asarray(data.train_x[: s["batch_size"]]))
    train_loss = {"init": float(stacked.eval_fn(fresh, *train)["loss"])}
    for name, agg in (("stacked", stacked), ("fused", fused)):
        check_model(agg.net, platform, f"server_ingest {name}")
        check(len(agg.history) == s["rounds"],
              f"{name}: {len(agg.history)} eval records")
        train_loss[name] = float(agg.eval_fn(agg.net, *train)["loss"])
        check(train_loss[name] < train_loss["init"],
              f"{name} did not learn: training loss {train_loss}")
    diff = max(float(np.max(np.abs(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64))))
               for a, b in zip(pack_pytree(stacked.net),
                               pack_pytree(fused.net)))
    check(diff <= INGEST_TOL,
          f"fused vs stacked max |diff| {diff} > {INGEST_TOL}")
    return {"max_abs_diff_fused_vs_stacked": diff, "train_loss": train_loss,
            "stacked_history": stacked.history,
            "fused_history": fused.history}


def client_mesh(seed: int, platform: str) -> dict:
    """--chips 4: the cohort sharded over a ('clients',) mesh of four chips
    against the single-device engine on the same seed, replicated and with
    the server state partitioned."""
    s = MESH
    data, task, cfg = _femnist_setup(
        seed, clients=s["clients"], per_round=s["per_round"],
        rounds=s["rounds"], batch_size=s["batch_size"],
        max_batches=s["max_batches"], lr=s["lr"], eval_every=1)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("clients",))
    keys = ("train_loss", "test_loss")  # test_acc moves in steps of 1/N

    def run(**kw):
        api = FedAvgAPI(data, task, cfg, **kw)
        api.train()
        check_model(api.net, platform, f"mesh run {sorted(kw)}")
        check(api.history[-1]["train_loss"] < api.history[0]["train_loss"],
              f"mesh run {sorted(kw)} did not learn: {api.history}")
        return api

    with jax.default_matmul_precision("highest"):
        single = run()
        meshed = {"replicated": run(mesh=mesh),
                  "sharded_state": run(mesh=mesh, shard_server_state=True)}
    out = {"single_history": single.history}
    for name, api in meshed.items():
        say("client_mesh_history", name=name, history=api.history,
            single=single.history)
        check(len(api.history) == len(single.history), f"{name}: rounds")
        gaps = [max(abs(a[k] - b[k]) for k in keys)
                for a, b in zip(api.history, single.history)]
        gap = max(gaps)
        check(gaps[0] <= MESH_FIRST_ROUND_TOL and gap <= MESH_TOL,
              f"{name}: history differs from single device by {gaps}")
        # the cohort's batch and the model really span the four chips
        batch_devs = {d for leaf in jax.tree.leaves(api._pack_round(0))
                      if hasattr(leaf, "devices") for d in leaf.devices()}
        leaves = jax.tree.leaves(api.net.params)
        model_devs = {d for leaf in leaves for d in leaf.devices()}
        split = [leaf for leaf in leaves
                 if leaf.addressable_shards[0].data.shape != leaf.shape]
        check(len(batch_devs) == 4, f"{name}: batch on {len(batch_devs)} devices")
        check(len(model_devs) == 4, f"{name}: model on {len(model_devs)} devices")
        check(bool(split) == (name == "sharded_state"),
              f"{name}: {len(split)} of {len(leaves)} leaves partitioned")
        out[name] = {"history_gap_per_round": gaps,
                     "batch_devices": len(batch_devs),
                     "model_devices": len(model_devs),
                     "partitioned_leaves": len(split), "leaves": len(leaves),
                     "placement": api._state_placement}
    return out


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 1

    perf.install()
    cache_dir = enable_compile_cache()
    say("start", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), chips=args.chips, seed=args.seed,
        jax=jax.__version__, compile_cache_dir=cache_dir,
        cache_entries_at_start=(len(os.listdir(cache_dir))
                                if os.path.isdir(cache_dir) else 0),
        host_packer="native" if native.native_available() else "numpy",
        bf16_peak_flops=bf16_peak())
    check(bf16_peak() is not None,
          f"device kind {dev.device_kind!r} matches no key of the peak table")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 4:
            phases = [("client_mesh", client_mesh)]
        else:
            phases = [("femnist_cnn",
                       lambda s, p: femnist_cnn(s, p, workdir)),
                      ("cifar_resnet56", cifar_resnet56),
                      ("flash_lm", flash_lm),
                      ("server_ingest", server_ingest)]
        t_all = time.perf_counter()
        for name, fn in phases:
            before, t0 = _compile_counters(), time.perf_counter()
            result = fn(args.seed, dev.platform)
            after = _compile_counters()
            mem = device_memory_stats()
            check(bool(mem), "memory_stats() gave nothing on this backend")
            say(name, seconds=time.perf_counter() - t0,
                **{k: after[k] - before[k] for k in after}, memory=mem,
                result=result)
    say("done", seconds=time.perf_counter() - t_all, **_compile_counters())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
