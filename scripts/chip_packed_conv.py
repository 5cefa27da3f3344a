"""By hand, on the chip: the width-packed 3x3 convolution against the plain
one, shape by shape, timed from a profiler trace (not the host clock).

    python3 scripts/chip_packed_conv.py [--reps 10] [--stages s1,s2]
        [--pack 2,4,8] [--only fwd,dw] [--no-gap]

For each of the CIFAR ResNet's four 3x3 shapes (the stem and the three
stages), at the benchmark cell's size (``vmap`` over 10 kernels, 64 images a
client, float32, matmul precision ``highest``):

  fwd, dx, dw   one convolution's forward, input gradient and kernel
                gradient alone: ``plain`` (``lax.conv_general_dilated``),
                ``packed`` (``ops/packed_conv.py``), and for dx also
                ``autodiff`` (the packed call's own transpose, which
                dilates ``dy`` by P); ``dw_packed`` lays ``dy`` out again,
                ``dw_swapped`` the saved input ``x`` (``grad_lays_out``)
  chain         value and gradients of conv-relu-conv, so that what the
                reshape between two packed calls costs shows; ``gradonly``
                packs the kernel gradient alone
  normchain     what the model runs: value and three gradients of
                conv - group norm - relu - conv - group norm, so that the
                norm's backward is what hands each convolution its ``dy``;
                ``plain``, and the rule's packing with the kernel gradient
                in each form, ``x`` and ``dy``
  gap           max |packed - plain| / max |plain| of output, input gradient
                and the kernel gradient in both forms at ``highest``, and of
                the same packed call at the TPU's default precision (one
                bf16 pass)

``--pack`` times the packed programs at each of the given pack factors in
place of the rule's (how the rule was narrowed: PERF.md section 6, PR 27);
``--only`` keeps the programs whose names start so.

Device time of a program is the union of its ops' intervals inside its own
``bench:`` annotation, over the repetitions. One JSON line on standard
output and in ``chiprun_out/packed_conv_micro.json``. Exits non-zero
without a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT = os.path.join(ROOT, "chiprun_out")
CLIENTS, BATCH = 10, 64
# name: (Cin, Cout, side)
STAGES = {"stem": (3, 16, 32), "s1": (16, 16, 32), "s2": (32, 32, 16),
          "s3": (64, 64, 8)}
TOP = 12


def forced(lays_out: str, fn):
    """``fn``, traced with every packed kernel gradient in the form that
    lays ``lays_out`` out again, whatever the rule says of the shape."""
    from fedml_tpu.ops import packed_conv as pc

    def traced(*args):
        rule = pc.grad_lays_out
        pc.grad_lays_out = lambda kernel_shape, p, p_grad: lays_out
        try:
            return fn(*args)
        finally:
            pc.grad_lays_out = rule
    return traced


def programs(cin: int, cout: int, p: int):
    """{name: function} for one shape at pack factor ``p``, each of one
    client's arrays (``_args`` says which)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax import lax
    from fedml_tpu.ops import packed_conv as pc

    def plain(x, w):
        return lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                        dimension_numbers=pc._NHWC)

    def raw(x, w):
        return pc._packed(x, w, p, None)

    def packed(x, w):
        return pc.packed_conv3x3(x, w, p, p, None)

    def grad_only(x, w):
        return pc.packed_conv3x3(x, w, 1, p, None)

    def dx_of(fn):
        return lambda x, w, dy: jax.vjp(lambda a: fn(a, w), x)[1](dy)[0]

    def dw_of(fn):
        return lambda x, w, dy: jax.vjp(lambda k: fn(x, k), w)[1](dy)[0]

    def chain_of(fn):
        def loss(x, w, w2, dy):
            return (fn(jax.nn.relu(fn(x, w)), w2) * dy).sum()
        return jax.value_and_grad(loss, (0, 1, 2))

    def normchain_of(fn):
        norm = nn.GroupNorm(num_groups=min(8, cout))
        affine = {"params": {"scale": jnp.full((cout,), 0.5),
                             "bias": jnp.full((cout,), 0.1)}}

        def loss(x, w, w2, dy):
            y = jax.nn.relu(norm.apply(affine, fn(x, w)))
            return (norm.apply(affine, fn(y, w2)) * dy).sum()
        return jax.value_and_grad(loss, (0, 1, 2))

    def by_rule(x, w):
        return pc.packed_conv3x3(
            x, w, pc.pack_factor(w.shape, (1, 1), x.shape[2], "tpu"), p, None)

    out = {"fwd_plain": plain, "fwd_packed": packed,
           "dw_plain": dw_of(plain), "dw_packed": dw_of(raw),
           "dw_swapped": forced("x", dw_of(grad_only))}
    if cin == cout:
        out.update({"dx_plain": dx_of(plain), "dx_autodiff": dx_of(raw),
                    "dx_packed": dx_of(packed),
                    "chain_plain": chain_of(plain),
                    "chain_autodiff": chain_of(raw),
                    "chain_packed": chain_of(packed),
                    "chain_gradonly": chain_of(grad_only),
                    "normchain_plain": normchain_of(plain),
                    "normchain_x": forced("x", normchain_of(by_rule)),
                    "normchain_dy": forced("dy", normchain_of(by_rule))})
    return out


def _args(name: str, x, w, w2, dy):
    if name.startswith("fwd"):
        return x, w
    if name.startswith(("chain", "normchain")):
        return x, w, w2, dy
    return x, w, dy


def gaps(p, x, w, dy):
    """Max relative gap of the packed call against the plain one at
    ``highest``, and of the packed call at default precision; the kernel
    gradient in both forms (``dw_x``, ``dw_dy``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from fedml_tpu.ops import packed_conv as pc

    def all3(fn):
        def f(x, w, dy):
            y, vjp = jax.vjp(fn, x, w)
            return (y, *vjp(dy))
        return jax.jit(jax.vmap(f))(x, w, dy)

    ref = all3(lambda x, w: lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=pc._NHWC,
        precision=lax.Precision.HIGHEST))
    out = {}
    for label, prec in (("highest", lax.Precision.HIGHEST),
                        ("default", lax.Precision.DEFAULT)):
        out[label] = {}
        for lays_out in ("x", "dy"):
            got = forced(lays_out, all3)(
                lambda x, w: pc.packed_conv3x3(x, w, p, p, prec))
            out[label].update(
                {k: float(jnp.abs(g - r).max() / jnp.abs(r).max())
                 for k, g, r in zip(("y", "dx", f"dw_{lays_out}"), got, ref)})
    return out


def main() -> int:
    import jax
    from benchmark import trace_reduce
    from fedml_tpu.ops import packed_conv as pc

    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--stages", default=",".join(STAGES))
    ap.add_argument("--pack", default="")
    ap.add_argument("--only", default="")
    ap.add_argument("--no-gap", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: jax found platform {dev.platform!r}")
    jax.config.update("jax_default_matmul_precision", "highest")

    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "reps": args.reps, "clients": CLIENTS, "batch": BATCH,
              "stages": {}}
    only = tuple(filter(None, args.only.split(",")))
    for stage in args.stages.split(","):
        cin, cout, side = STAGES[stage]
        # the P that fills the columns: the rule's for the kernel gradient
        rule = pc.pack_factor((3, 3, cin, cout), (1, 1), side, "tpu",
                              grad=True)
        packs = [int(v) for v in args.pack.split(",") if v] or [rule]
        packs = [v for v in packs if side % v == 0]
        k = jax.random.split(jax.random.PRNGKey(27), 4)
        x = jax.random.normal(k[0], (CLIENTS, BATCH, side, side, cin))
        w = jax.random.normal(k[1], (CLIENTS, 3, 3, cin, cout)) * 0.1
        w2 = jax.random.normal(k[2], (CLIENTS, 3, 3, cout, cout)) * 0.1
        dy = jax.random.normal(k[3], (CLIENTS, BATCH, side, side, cout))
        jitted = {}
        for p in packs:
            for name, fn in programs(cin, cout, p).items():
                if only and not name.startswith(only):
                    continue
                if not name.endswith("_plain"):
                    name = f"{name}_p{p}"
                jitted.setdefault(name, jax.jit(jax.vmap(fn)))
        for name, fn in jitted.items():  # compile and warm
            jax.block_until_ready(fn(*_args(name, x, w, w2, dy)))
        tracedir = tempfile.mkdtemp(prefix="packed_conv_")
        with jax.profiler.trace(tracedir):
            for name, fn in jitted.items():
                a = _args(name, x, w, w2, dy)
                with jax.profiler.TraceAnnotation(f"bench:{name}"):
                    for _ in range(args.reps):
                        o = fn(*a)
                    jax.block_until_ready(o)
        device_ops, spans = trace_reduce.read_xplane(glob.glob(os.path.join(
            tracedir, "plugins", "profile", "*", "*.xplane.pb"))[0])
        shutil.rmtree(tracedir, ignore_errors=True)
        ops, = device_ops.values()
        rec = {"rule_p": rule, "ms": {}, "ops": {}}
        for label, lo, hi in spans:
            # block_until_ready sits inside the annotation: a program's ops
            # all run between its span's start and end
            leaf = [(n, c, s, e) for n, c, s, e in ops
                    if c not in trace_reduce.CONTROL_CATEGORIES
                    and lo <= s and e <= hi]
            per = {}
            for n, c, s, e in leaf:
                per[f"{n} [{c}]"] = per.get(f"{n} [{c}]", 0.0) + (e - s)
            rec["ms"][label] = 1e3 * trace_reduce.union_seconds(
                [(s, e) for _, _, s, e in leaf]) / args.reps
            rec["ops"][label] = [[n, 1e3 * t / args.reps] for n, t in
                                 sorted(per.items(), key=lambda kv: -kv[1])[:TOP]]
        if not args.no_gap:
            rec["gap"] = gaps(rule, x, w, dy)
        result["stages"][stage] = rec
        print(stage, json.dumps(rec["ms"]), json.dumps(rec.get("gap")),
              file=sys.stderr, flush=True)
    line = json.dumps(result)
    print(line, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "packed_conv_micro.json"), "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
