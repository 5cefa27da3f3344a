"""Long-context training throughput: tokens/sec vs sequence length.

The reference has no long-context story (its NLP models are tiny LSTMs);
this framework treats it as first-class (SP engine, ring/Ulysses/flash
attention). This bench puts a NUMBER on it: a TransformerLM training step
(fwd+bwd+SGD, jitted once per shape) timed across sequence lengths, with
the attention core either the Pallas flash kernel (``--flash 1``, default —
O(T) memory blockwise kernel, ops/flash_attention.py) or dense XLA
attention (``--flash 0``, O(T^2) scores materialized) for the kernel's
speedup/memory story on real Mosaic.

One JSON line per (seq_len, impl): tokens/sec, step latency, device.
A point that fails (e.g. dense OOM at long T — that IS the story) prints
an error line and the sweep continues.

Usage: python scripts/bench_longctx.py [--seqs 1024,2048,4096,8192]
       [--flash 1] [--batch 2] [--dim 256] [--depth 4] [--steps 8]
--flash 2 runs flash and dense side by side.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


_FWD_FLOPS_MEMO: dict[int, float | None] = {}


def _one_point(args, T: int, use_flash: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from fedml_tpu.core.tasks import sequence_task
    from fedml_tpu.models.transformer import TransformerLM

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randint(1, args.vocab, size=(args.batch, T)), jnp.int32)
    task = sequence_task(TransformerLM(
        vocab_size=args.vocab, dim=args.dim, depth=args.depth,
        num_heads=args.heads, max_len=T, use_flash=use_flash))
    net = task.init(jax.random.PRNGKey(0), x)
    opt = optax.sgd(0.1)
    opt_state = opt.init(net.params)
    key = jax.random.PRNGKey(1)
    mask = jnp.ones((args.batch,), jnp.float32)

    @jax.jit
    def step(params, extra, opt_state, x):
        (loss, _), grads = jax.value_and_grad(
            lambda p: task.loss(p, extra, x, x, mask, key, True)[:2],
            has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    params = net.params
    params, opt_state, loss = step(params, net.extra, opt_state, x)  # compile
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt_state, loss = step(params, net.extra, opt_state, x)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    rec = {
        "seq_len": T,
        "impl": "flash" if use_flash else "dense",
        "tokens_per_sec": round(args.batch * T * args.steps / dt, 1),
        "step_seconds": round(dt / args.steps, 4),
        "loss": round(float(loss), 4),
        "batch": args.batch, "dim": args.dim, "depth": args.depth,
        "device": jax.devices()[0].platform,
    }
    # MFU (TPU only): XLA's FLOP count of the compiled forward per token,
    # 3x-forward train accounting (utils/flops.py). The flash kernel hides
    # its inner FLOPs from cost analysis, so quote the DENSE forward's
    # count for both impls — same math, comparable MFU.
    from fedml_tpu.utils.flops import compiled_flops, train_mfu

    if T not in _FWD_FLOPS_MEMO:  # one cost-analysis compile per seq_len
        dense = sequence_task(TransformerLM(
            vocab_size=args.vocab, dim=args.dim, depth=args.depth,
            num_heads=args.heads, max_len=T, use_flash=False))
        _FWD_FLOPS_MEMO[T] = compiled_flops(dense.predict, params,
                                            net.extra, x)
    fwd = _FWD_FLOPS_MEMO[T]
    if fwd:
        # step is a plain single-device jit: tokens_per_sec IS per-chip
        mfu = train_mfu(rec["tokens_per_sec"], fwd / (args.batch * T))
        if mfu is not None:
            rec["mfu_vs_bf16_peak"] = round(mfu, 5)
    print(json.dumps(rec), flush=True)


def main():
    from fedml_tpu.utils.metrics import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=str, default="1024,2048,4096,8192")
    ap.add_argument("--flash", type=int, default=1,
                    help="1: Pallas flash kernel; 0: dense XLA attention; "
                         "2: both per point")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()

    impls = [True, False] if args.flash == 2 else [bool(args.flash)]
    for T in [int(s) for s in args.seqs.split(",")]:
        for use_flash in impls:
            try:
                _one_point(args, T, use_flash)
            except Exception as e:  # noqa: BLE001 — later points still run
                print(json.dumps({
                    "seq_len": T, "impl": "flash" if use_flash else "dense",
                    "error": f"{type(e).__name__}: {e}"[:200]}), flush=True)


if __name__ == "__main__":
    main()
