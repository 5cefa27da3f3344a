"""Fused on-device aggregation + bf16 client compute (docs/PERFORMANCE.md
§Fused aggregation / §Mixed precision).

Contracts enforced here:

- the streaming :class:`~fedml_tpu.core.fused_agg.PairwiseAccumulator`
  reproduces the stacked ``sum_assoc='pairwise'`` fold BIT FOR BIT across
  slot counts, arrival orders, and gate rejects;
- fused ≡ stacked end-to-end over the loopback runtime: dense / lossless
  tiers bitwise (model bits AND quarantine ledger), lossy tiers within
  codec tolerance with ledger equality — including a NaN adversary dying
  at the in-graph gate with NO host densify;
- the stacked staging path performs no host round-trips on staged uploads
  (the `_stack_uploads` no-transfer pin);
- bf16 off is bit-identical to the pre-policy engine across every driver
  (per-round, scanned block, pipelined, mesh), bf16 on agrees with itself
  across the same drivers, keeps f32 masters, and converges within 0.02
  of f32 at matched rounds;
- warmup precompiles the precision x bucket variants through the
  persistent compile cache (repeat run: zero fresh compiles).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.models.linear import LogisticRegression


def _data(seed=0):
    return synthetic_images(num_clients=8, image_shape=(6, 6, 1),
                            num_classes=3, samples_per_client=12,
                            test_samples=24, seed=seed)


def _task():
    return classification_task(LogisticRegression(num_classes=3))


def _cfg(**kw):
    base = dict(comm_round=3, client_num_in_total=8, client_num_per_round=4,
                batch_size=6, lr=0.1, frequency_of_the_test=100)
    base.update(kw)
    return FedAvgConfig(**base)


def _nan_adv():
    from fedml_tpu.chaos import AdversaryPlan

    return AdversaryPlan.from_json(
        {"seed": 1, "rules": [{"attack": "nan", "ranks": [2]}]})


def _leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


# ------------------------------------------------------------- accumulator
_FOLD_KS = (1, 2, 3, 4, 5, 7, 8)


@pytest.mark.parametrize("K", _FOLD_KS)
def test_accumulator_matches_stacked_pairwise_fold(K, request):
    """K sweep x shuffled arrival order x a gate reject: the streaming
    fold's bits equal the one-jit stacked gagg (norm_mult=inf, pairwise),
    reasons included — the composition the end-to-end parity rests on."""
    if K == 2 and jax.default_backend() == "cpu":
        request.applymarker(pytest.mark.xfail(strict=False, reason=(
            "XLA:CPU's LLVM fp-op fusion contracts the scalar tail of the "
            "3-element leaf's u0*w0 + u1*w1 one way in the one-jit stacked "
            "program and the other way in the fused one; optimization_"
            "barrier, reduce_precision and a select guard do not block it. "
            "On the chip fused and stacked are bitwise equal end to end "
            "(CHANGES.md PR 22)")))
    import random
    from functools import partial

    from fedml_tpu.core import fused_agg as F
    from fedml_tpu.core.robust_agg import gated_aggregate

    rs = np.random.RandomState(1)
    shapes = [(36, 3), (3,), (17, 5)]
    glob = [rs.randn(*s).astype(np.float32) for s in shapes]
    meta = F._leaf_meta(glob)
    fn = F.make_fused_ingest("dense", meta)
    gg = jax.jit(partial(gated_aggregate, robust_fn=None,
                         norm_mult=float("inf"), pairwise=True))
    # every case draws what the sweep's earlier cases drew before it, so
    # each K folds the data it folded when this was one loop
    for k in _FOLD_KS[:_FOLD_KS.index(K) + 1]:
        ups = [[rs.randn(*s).astype(np.float32) for s in shapes]
               for _ in range(k)]
    if K >= 3:
        ups[2][0][0, 0] = np.nan
    w = [10.0 + i for i in range(K)]
    stacked = [jnp.stack([u[i] for u in ups]) for i in range(len(shapes))]
    avg, _, reasons = gg(stacked, [jnp.asarray(g) for g in glob],
                         jnp.asarray(w, jnp.float32))
    fr = F.FusedRoundIngest([jnp.asarray(g) for g in glob], meta)
    order = list(range(K))
    random.Random(K).shuffle(order)
    for i in order:
        fr.add(i, fn, [jnp.asarray(x) for x in ups[i]], None, None, w[i])
    new_leaves, reasons2 = fr.flush()
    assert _leaves_equal(avg, new_leaves), f"K={K} model bits diverged"
    np.testing.assert_array_equal(np.asarray(reasons),
                                  np.asarray(reasons2))


def test_accumulator_in_order_memory_is_logarithmic():
    from fedml_tpu.core import fused_agg as F

    glob = [np.zeros((4, 4), np.float32)]
    meta = F._leaf_meta(glob)
    fn = F.make_fused_ingest("dense", meta)
    fr = F.FusedRoundIngest([jnp.asarray(g) for g in glob], meta)
    K = 64
    for i in range(K):
        fr.add(i, fn, [jnp.ones((4, 4), np.float32)], None, None, 1.0)
    # in slot order the live set is the binary counter: <= log2(K) + 1
    assert fr.peak_terms <= int(np.log2(K)) + 1, fr.peak_terms


def test_fused_duplicate_slot_folds_exactly_once():
    from fedml_tpu.core import fused_agg as F

    glob = [np.zeros((2,), np.float32)]
    meta = F._leaf_meta(glob)
    fn = F.make_fused_ingest("dense", meta)
    fr = F.FusedRoundIngest([jnp.asarray(g) for g in glob], meta)
    up = [np.ones((2,), np.float32)]
    fr.add(0, fn, up, None, None, 5.0)
    fr.add(0, fn, up, None, None, 5.0)  # chaos duplicate: ignored
    leaves, _ = fr.flush()
    np.testing.assert_allclose(np.asarray(leaves[0]), [1.0, 1.0])


# ------------------------------------------------------- end-to-end parity
def test_fused_equals_stacked_dense_bitwise_with_ledger():
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task, cfg = _data(), _task(), _cfg()
    a = run_simulated(data, task, cfg, job_id="fb-stacked",
                      sum_assoc="pairwise", adversary_plan=_nan_adv())
    b = run_simulated(data, task, cfg, job_id="fb-fused", fused_agg=True,
                      adversary_plan=_nan_adv())
    assert _leaves_equal(pack_pytree(a.net), pack_pytree(b.net))
    assert a.quarantine.canonical() == b.quarantine.canonical()
    assert b.quarantine.canonical(), "NaN adversary never quarantined"
    assert b.fused_agg and b.agg_record().get("fused") is True
    assert b.agg_record().get("flush_s") is not None


@pytest.mark.parametrize("tier_kw,exact", [
    ({"update_codec": "delta"}, True),
    ({"sparsify_ratio": 0.3}, True),
    ({"update_codec": "delta-sign1"}, True),
    ({"update_codec": "delta-int8"}, False),
])
def test_fused_codec_tiers_match_stacked(tier_kw, exact):
    """Lossless/dense-equivalent tiers are bitwise; delta-int8's on-device
    dequant may fma the scale-multiply into the base add (a few ulps vs
    the host decode) — within codec tolerance, ledger equal either way."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task, cfg = _data(), _task(), _cfg()
    a = run_simulated(data, task, cfg, job_id=f"fb-s-{exact}",
                      sum_assoc="pairwise", adversary_plan=_nan_adv(),
                      **tier_kw)
    b = run_simulated(data, task, cfg, job_id=f"fb-f-{exact}",
                      fused_agg=True, adversary_plan=_nan_adv(), **tier_kw)
    assert a.quarantine.canonical() == b.quarantine.canonical()
    assert b.quarantine.canonical(), "NaN adversary never quarantined"
    if exact:
        assert _leaves_equal(pack_pytree(a.net), pack_pytree(b.net))
    else:
        for x, y in zip(pack_pytree(a.net), pack_pytree(b.net)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=0, atol=1e-6)
    assert all(np.isfinite(np.asarray(v)).all() for v in pack_pytree(b.net))


def test_fused_no_host_densify(monkeypatch):
    """The fused server must never touch the host densify path: the
    server-side decoders raise if called (the client-side EF residual uses
    decode_update, which stays live — only apply_delta/topk_decode are
    server-only)."""
    from fedml_tpu.comm import delta as delta_mod
    from fedml_tpu.comm import sparse as sparse_mod
    from fedml_tpu.distributed.fedavg import run_simulated

    def _boom(*a, **kw):
        raise AssertionError("host densify called on the fused path")

    monkeypatch.setattr(delta_mod, "apply_delta", _boom)
    monkeypatch.setattr(sparse_mod, "topk_decode", _boom)
    data, task, cfg = _data(), _task(), _cfg()
    b = run_simulated(data, task, cfg, job_id="fb-nodense", fused_agg=True,
                      update_codec="delta-int8", adversary_plan=_nan_adv())
    assert b.quarantine.canonical(), "NaN adversary never quarantined"
    assert all(np.isfinite(np.asarray(v)).all() for v in pack_pytree(b.net))


def test_fused_elastic_partial_matches_stacked_subset():
    """A straggler hole in the slot order: the cursor pends, the flush
    skips the hole, and the fold equals the stacked compacted subset."""
    from functools import partial

    from fedml_tpu.core import fused_agg as F
    from fedml_tpu.core.robust_agg import gated_aggregate

    rs = np.random.RandomState(3)
    glob = [rs.randn(5, 2).astype(np.float32)]
    meta = F._leaf_meta(glob)
    fn = F.make_fused_ingest("dense", meta)
    ups = [[rs.randn(5, 2).astype(np.float32)] for _ in range(5)]
    arrived = [0, 1, 3, 4]  # slot 2 never arrives
    stacked = [jnp.stack([ups[i][0] for i in arrived])]
    gg = jax.jit(partial(gated_aggregate, robust_fn=None,
                         norm_mult=float("inf"), pairwise=True))
    avg, _, _ = gg(stacked, [jnp.asarray(g) for g in glob],
                   jnp.asarray([10., 11., 13., 14.], jnp.float32))
    fr = F.FusedRoundIngest([jnp.asarray(g) for g in glob], meta)
    for i, w in zip(arrived, (10., 11., 13., 14.)):
        fr.add(i, fn, [jnp.asarray(ups[i][0])], None, None, w)
    leaves, _ = fr.flush()
    assert _leaves_equal(avg, leaves)


def test_inflate_update_structural_garbage_raises():
    import zlib

    from fedml_tpu.comm.delta import (CorruptPayload, encode_update,
                                      inflate_update, round_delta)

    rs = np.random.RandomState(0)
    local = [rs.randn(16, 4).astype(np.float32)]
    base = [np.zeros((16, 4), np.float32)]
    payload, scales = encode_update(round_delta(local, base), "delta-int8")
    # truncated deflate stream
    with pytest.raises(CorruptPayload):
        inflate_update([payload[0][:3]], scales, "delta-int8", base)
    # leaf-count mismatch
    with pytest.raises(CorruptPayload):
        inflate_update([], scales, "delta-int8", base)
    # wrong entry count behind a valid deflate stream
    bad = np.frombuffer(zlib.compress(np.zeros(7, np.int8).tobytes()),
                        np.uint8)
    with pytest.raises(CorruptPayload):
        inflate_update([bad], scales, "delta-int8", base)
    # the valid payload round-trips to the raw int8 array
    raw, sc = inflate_update(payload, scales, "delta-int8", base)
    assert raw[0].dtype == np.int8 and raw[0].size == 64
    np.testing.assert_array_equal(sc, np.atleast_1d(scales))
    # wrong-sized NON-float dense leaf: structural garbage caught HERE,
    # never a reshape trace error inside the server's receive loop
    local2 = [rs.randn(4).astype(np.float32), np.arange(4, dtype=np.int64)]
    base2 = [np.zeros(4, np.float32), np.zeros(4, np.int64)]
    payload2, scales2 = encode_update(round_delta(local2, base2),
                                      "delta-int8")
    with pytest.raises(CorruptPayload):
        inflate_update([payload2[0], np.arange(7, dtype=np.int64)],
                       scales2, "delta-int8", base2)


def test_fused_refusals_are_loud():
    """PR-21: the --fused_agg refusal matrix shrinks to ONE documented
    cell — host-representation aggregates, whose ``aggregate()`` consumes
    the host stack the fused plane exists to avoid (TurboAggregate keeps
    its own mod-p fused path). Every former refusal is a composition
    now: robust estimators / armed sanitize (staged fused mode),
    shard_server_state (flush-layout property), async_buffer_k (densify
    at the door, gate at drain), edges (fused edge-tier ingest)."""
    from fedml_tpu.distributed.fedavg.aggregator import FedAvgAggregator
    from fedml_tpu.distributed.fedavg_robust import FedAvgRobustAggregator

    data, task, cfg = _data(), _task(), _cfg()
    with pytest.raises(ValueError, match="HOST representation"):
        FedAvgRobustAggregator(data, task, cfg, worker_num=4,
                               fused_agg=True)
    # the lifted rows construct — and stay on the fused route
    agg = FedAvgAggregator(data, task, cfg, worker_num=4, fused_agg=True)
    assert agg.sum_assoc == "pairwise"  # fused IS the canonical pairwise
    assert not agg._fused_staged       # plain keeps fold-at-arrival
    for kw in ({"aggregator": "median"}, {"sanitize": True},
               {"aggregator": "krum",
                "aggregator_params": {"f": 1}}):
        a = FedAvgAggregator(data, task, cfg, worker_num=6,
                             fused_agg=True, **kw)
        assert a.fused_agg and a._fused_staged


def test_stacked_staging_stacks_without_transfers():
    """Satellite pin: staged device-resident uploads stack straight from
    their placements — no host round-trip per rank per leaf."""
    from fedml_tpu.distributed.fedavg.aggregator import FedAvgAggregator

    data, task, cfg = _data(), _task(), _cfg()
    agg = FedAvgAggregator(data, task, cfg, worker_num=4)
    leaves = [np.asarray(v) for v in pack_pytree(agg.net)]
    for r in range(4):
        agg.add_local_trained_result(r, [np.array(v) for v in leaves],
                                     10, None)
    ranks = sorted(agg.model_dict)
    assert all(isinstance(v, jax.Array) for v in agg.model_dict[ranks[0]])
    with jax.transfer_guard("disallow"):
        stacked = agg._stack_uploads(ranks)
    assert stacked[0].shape[0] == 4


# -------------------------------------------------------- bf16 tentpole
def test_f32_explicit_is_bitwise_the_default_engine():
    """precision='f32' must trace NO casts: per-round, scanned-block,
    pipelined, and mesh drivers all produce the default engine's bits."""
    from jax.sharding import Mesh

    data, task = _data(), _task()
    cfg = _cfg()
    cfg32 = dataclasses.replace(cfg, precision="f32")
    a = FedAvgAPI(data, task, cfg)
    b = FedAvgAPI(data, task, cfg32)
    for r in range(3):
        a.run_round(r)
        b.run_round(r)
    assert _leaves_equal(jax.tree.leaves(a.net.params),
                         jax.tree.leaves(b.net.params))
    c = FedAvgAPI(data, task, cfg, device_data=True)
    d = FedAvgAPI(data, task, cfg32, device_data=True)
    c.run_rounds(0, 3)
    d.run_rounds(0, 3)
    assert _leaves_equal(jax.tree.leaves(c.net.params),
                         jax.tree.leaves(d.net.params))
    e = FedAvgAPI(data, task, cfg32, prefetch=2)
    e.run_pipelined(0, 3)
    assert _leaves_equal(jax.tree.leaves(a.net.params),
                         jax.tree.leaves(e.net.params))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("clients",))
    f = FedAvgAPI(data, task, cfg, mesh=mesh)
    g = FedAvgAPI(data, task, cfg32, mesh=mesh)
    for r in range(2):
        f.run_round(r)
        g.run_round(r)
    assert _leaves_equal(jax.tree.leaves(f.net.params),
                         jax.tree.leaves(g.net.params))


def test_bf16_driver_parity_and_f32_masters():
    """bf16 on: the cast is real (bits differ from f32), the MASTER
    weights stay f32, and per-round ≡ pipelined ≡ scanned-block ≡ mesh
    per-round-vs-block bitwise."""
    from jax.sharding import Mesh

    data, task = _data(), _task()
    cfg16 = _cfg(precision="bf16")
    a32 = FedAvgAPI(data, task, _cfg())
    a = FedAvgAPI(data, task, cfg16)
    for r in range(3):
        a32.run_round(r)
        a.run_round(r)
    assert not _leaves_equal(jax.tree.leaves(a32.net.params),
                             jax.tree.leaves(a.net.params))
    assert all(np.asarray(v).dtype == np.float32
               for v in jax.tree.leaves(a.net.params))
    b = FedAvgAPI(data, task, cfg16, prefetch=2)
    b.run_pipelined(0, 3)
    assert _leaves_equal(jax.tree.leaves(a.net.params),
                         jax.tree.leaves(b.net.params))
    c = FedAvgAPI(data, task, cfg16, device_data=True)
    c.run_rounds(0, 3)
    assert _leaves_equal(jax.tree.leaves(a.net.params),
                         jax.tree.leaves(c.net.params))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("clients",))
    d = FedAvgAPI(data, task, cfg16, mesh=mesh, device_data=True)
    e = FedAvgAPI(data, task, cfg16, mesh=mesh, device_data=True)
    for r in range(2):
        d.run_round(r)
    e.run_rounds(0, 2)
    assert _leaves_equal(jax.tree.leaves(d.net.params),
                         jax.tree.leaves(e.net.params))


def test_bf16_convergence_within_002_of_f32():
    data, task = _data(), _task()
    cfg = _cfg(comm_round=6)
    a = FedAvgAPI(data, task, cfg)
    b = FedAvgAPI(data, task, dataclasses.replace(cfg, precision="bf16"))
    for r in range(6):
        a.run_round(r)
        b.run_round(r)
    ea, eb = a.evaluate(), b.evaluate()
    assert abs(float(ea["loss"]) - float(eb["loss"])) < 0.02, (ea, eb)
    assert abs(float(ea["acc"]) - float(eb["acc"])) <= 0.02, (ea, eb)


def test_bf16_composes_with_fused_cross_process():
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = _data(), _task()
    cfg16 = _cfg(precision="bf16")
    a = run_simulated(data, task, cfg16, job_id="fb16-stacked",
                      sum_assoc="pairwise", adversary_plan=_nan_adv())
    b = run_simulated(data, task, cfg16, job_id="fb16-fused",
                      fused_agg=True, adversary_plan=_nan_adv())
    assert _leaves_equal(pack_pytree(a.net), pack_pytree(b.net))
    assert a.quarantine.canonical() == b.quarantine.canonical()
    assert b.quarantine.canonical()


def test_precision_validation_is_loud():
    from fedml_tpu.core.local import LocalSpec, make_local_update

    data, task = _data(), _task()
    with pytest.raises(ValueError, match="precision"):
        FedAvgAPI(data, task, _cfg(precision="fp8"))
    import optax

    with pytest.raises(ValueError, match="compute_dtype"):
        make_local_update(task, LocalSpec(optimizer=optax.sgd(0.1),
                                         compute_dtype="tf32"))


def test_warmup_precision_bucket_variants_zero_fresh_on_repeat(tmp_path):
    """The bf16 x bucket-ladder variants precompile through the persistent
    cache: a repeat warmup performs ZERO fresh compiles (the warm-run
    contract of docs/PERFORMANCE.md §Mixed precision)."""
    data, task = _data(), _task()
    cfg16 = _cfg(precision="bf16")
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        a = FedAvgAPI(data, task, cfg16, bucket_batches=True)
        rep = a.warmup()
        assert all(v.startswith("round_bf16_b") for v in rep["variants"])
        if not rep["instrumented"]:
            pytest.skip("jax.monitoring unavailable")
        assert rep["fresh_compiles"] > 0
        b = FedAvgAPI(data, task, cfg16, bucket_batches=True)
        rep2 = b.warmup()
        assert rep2["variants"] == rep["variants"]
        assert rep2["fresh_compiles"] == 0, rep2
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)


# ------------------------------------------------------------- reporting
def test_report_renders_flush_and_precision_columns():
    from scripts.report import render_table

    new = [{"kind": "round", "round": 0, "clients": [1, 2],
            "metrics": {"loss_sum": 1.0, "count": 2.0},
            "agg": {"mode": "replicated", "fused": True,
                    "flush_s": 0.012, "stack_bytes": 4096,
                    "prec": "bf16"}}]
    out = render_table(new)
    assert "flush_s" in out and "prec" in out and "bf16" in out
    old = [{"kind": "round", "round": 0, "clients": [1],
            "metrics": {"loss_sum": 1.0, "count": 2.0}}]
    out_old = render_table(old)
    assert "flush_s" not in out_old and "prec" not in out_old


def test_fused_flush_metrics_exported():
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs.metrics import REGISTRY

    data, task, cfg = _data(), _task(), _cfg()
    run_simulated(data, task, cfg, job_id="fb-metrics", fused_agg=True)
    snap = REGISTRY.snapshot()
    assert "fed_flush_seconds" in snap, \
        sorted(k for k in snap if k.startswith("fed_"))
    stack = snap.get("fed_agg_stack_bytes", {})
    assert any("mode=fused" in k for k in stack), stack


def test_fused_staged_stack_bytes_budget():
    """Memory honesty for the STAGED fused mode (PR-21,
    docs/PERFORMANCE.md §Fused aggregation): robust gating keeps every
    staged slot live until the verdict flush, so the device-staged bytes
    are the stacked route's stack bytes PLUS the per-slot evidence rows —
    O(K), not plain mode's O(log K) — exported under their own gauge mode
    (``fed_agg_stack_bytes{mode=fused_staged}``) and pinned here to the
    exact budget formula the aggregator reports."""
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs.metrics import REGISTRY

    data, task, cfg = _data(), _task(), _cfg()
    agg = run_simulated(data, task, cfg, job_id="fb-staged-mem",
                        fused_agg=True, aggregator="median")
    snap = REGISTRY.snapshot()
    stack = snap.get("fed_agg_stack_bytes", {})
    staged = [v for k, v in stack.items() if "mode=fused_staged" in k]
    assert staged, stack
    K = cfg.client_num_per_round
    budget = K * (agg._fused_term_nbytes
                  + 4 * (agg._fused_sketch_dim + 3))
    assert staged[0] == budget, (staged[0], budget)
    # the staged premium over a stacked barrier is ONLY the evidence rows
    # (norm + finite + weight + sketch floats per slot) — the tradeoff
    # bought: no host densify, no barrier H2D burst, decode overlapped
    # with the wire wait
    assert staged[0] - K * agg._model_nbytes == \
        K * 4 * (agg._fused_sketch_dim + 3)
