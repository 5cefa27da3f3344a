"""Wire-efficiency layer (docs/PERFORMANCE.md §Wire efficiency): round-delta
encoding against version-stamped bases, int8/1-bit quantization with shared
error feedback (comm/delta.py + comm/ef.py), delta broadcast with dense
fallback, sanitation-gate composition for decoded garbage, per-direction
byte accounting, and the async-waves composition that lifts the PR-8
dense-uploads-only refusal.

Oracles are numpy; end-to-end claims run the loopback cross-process stack
at tiny shapes. The byte-reduction floors (>= 8x int8, >= 25x 1-bit vs
dense f32) are asserted here on a model large enough that frame headers
don't dilute the ratio; convergence against bytes has no chip measurement
(ROADMAP R11).
"""

import threading
import types

import numpy as np
import pytest

from fedml_tpu.comm.delta import (CorruptPayload, apply_delta, decode_update,
                                  encode_update, payload_nbytes, round_delta)
from fedml_tpu.comm.ef import ErrorFeedback
from fedml_tpu.comm.message import Message, pack_pytree


# ------------------------------------------------------------------ fixtures
@pytest.fixture(scope="module")
def lr_setup():
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.linear import LogisticRegression

    data = synthetic_images(num_clients=8, image_shape=(8, 8, 1), num_classes=4,
                            samples_per_client=24, test_samples=96, seed=3)
    task = classification_task(LogisticRegression(num_classes=4))
    return data, task


def _cfg(rounds=3, per_round=4, seed=0, lr=0.1):
    from fedml_tpu.algorithms.fedavg import FedAvgConfig

    return FedAvgConfig(comm_round=rounds, client_num_in_total=8,
                        client_num_per_round=per_round, epochs=1, batch_size=8,
                        lr=lr, frequency_of_the_test=1, seed=seed)


# ----------------------------------------------------------- codec oracles
def test_int8_delta_roundtrip_oracle():
    """decode(encode(d)) is within half a quantization step of d per entry
    (deadzone off); non-float leaves ride dense and apply_delta REPLACES
    the base with them; the round_delta/apply_delta pair inverts."""
    rs = np.random.RandomState(0)
    local = [rs.randn(33, 7).astype(np.float32) * 3,
             rs.randn(11).astype(np.float32),
             np.arange(5, dtype=np.int64)]
    base = [rs.randn(33, 7).astype(np.float32),
            rs.randn(11).astype(np.float32),
            np.zeros(5, np.int64)]
    delta = round_delta(local, base)
    payload, scales = encode_update(delta, "delta-int8", deadzone=0.0)
    dec = decode_update(payload, scales, "delta-int8", base)
    for d, g, s in zip(dec[:2], delta[:2], scales[:2]):
        assert np.max(np.abs(d - g)) <= s / 2 + 1e-7
    np.testing.assert_array_equal(dec[2], local[2])  # dense passthrough
    eff = apply_delta(base, dec)
    np.testing.assert_array_equal(eff[2], local[2])
    for e, w, s in zip(eff[:2], local[:2], scales[:2]):
        assert np.max(np.abs(e - w)) <= s / 2 + 1e-6


def test_int8_scale_edge_cases():
    """All-zero tensor -> zeros with scale 0 (no divide); single-element
    -> round-trips to itself within a ulp of the scale math; empty leaf
    survives; non-finite input decodes NON-FINITE (poison propagated to
    the sanitation gate, never laundered to zeros)."""
    zero = [np.zeros((5, 5), np.float32)]
    one = [np.array([-3.25], np.float32)]
    empty = [np.zeros((0,), np.float32)]
    for codec in ("delta-int8", "delta-sign1"):
        p, s = encode_update(zero, codec, deadzone=0.0)
        np.testing.assert_array_equal(
            decode_update(p, s, codec, zero)[0], zero[0])
        p, s = encode_update(empty, codec)
        assert decode_update(p, s, codec, empty)[0].shape == (0,)
    p, s = encode_update(one, "delta-int8", deadzone=0.0)
    np.testing.assert_allclose(decode_update(p, s, "delta-int8", one)[0],
                               one[0], rtol=1e-6)
    # the DEFAULT deadzone must not starve single-element/uniform-|d|
    # leaves (|d| == rms < deadzone*rms would hold forever; the threshold
    # caps at the leaf max so the top entries always transmit)
    p, s = encode_update(one, "delta-int8")
    np.testing.assert_allclose(decode_update(p, s, "delta-int8", one)[0],
                               one[0], rtol=1e-6)
    uni = [np.full((7,), 0.5, np.float32)]
    p, s = encode_update(uni, "delta-int8")
    np.testing.assert_allclose(decode_update(p, s, "delta-int8", uni)[0],
                               uni[0], rtol=1e-6)
    # non-finite input: the scale goes NaN, the decode is non-finite
    # everywhere — exactly what the PR-4 gate quarantines
    for codec in ("delta-int8", "delta-sign1"):
        bad = [np.array([1.0, np.nan, 2.0], np.float32)]
        p, s = encode_update(bad, codec)
        assert not np.isfinite(s[0])
        dec = decode_update(p, s, codec, bad)[0]
        assert not np.isfinite(dec).any()
        inf = [np.array([1.0, np.inf], np.float32)]
        p, s = encode_update(inf, codec)
        assert not np.isfinite(decode_update(p, s, codec, inf)[0]).all()


def test_sign1_roundtrip_oracle_and_payload_shrink():
    """1-bit tier: decode is sign(d) * mean|d| per tensor; the payload is
    >= 25x smaller than the f32 leaf it encodes (1 bit vs 32 + one scale)."""
    rs = np.random.RandomState(1)
    d = [rs.randn(257, 31).astype(np.float32)]
    payload, scales = encode_update(d, "delta-sign1")
    dec = decode_update(payload, scales, "delta-sign1", d)[0]
    np.testing.assert_allclose(np.abs(dec),
                               np.mean(np.abs(d[0])), rtol=1e-6)
    signs_match = np.sign(dec) == np.where(d[0] >= 0, 1.0, -1.0)
    assert signs_match.all()
    assert d[0].nbytes / payload_nbytes(payload, scales) >= 25.0


def test_error_feedback_conserves_mass():
    """shipped + residual == compensated, exactly, for every float leaf;
    non-float leaves carry zero residual; a second round folds the
    residual back in (compensate)."""
    rs = np.random.RandomState(2)
    delta = [rs.randn(16, 4).astype(np.float32),
             np.arange(3, dtype=np.int64)]
    ef = ErrorFeedback()
    comp = ef.compensate(delta)
    np.testing.assert_array_equal(comp[0], delta[0])  # no residual yet
    payload, scales = encode_update(comp, "delta-int8")
    shipped = decode_update(payload, scales, "delta-int8", delta)
    ef.update(comp, shipped)
    np.testing.assert_allclose(shipped[0] + ef.residual[0], comp[0],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(ef.residual[1], np.zeros(3, np.int64))
    comp2 = ef.compensate(delta)
    np.testing.assert_allclose(comp2[0], delta[0] + ef.residual[0],
                               rtol=1e-6)


def test_ef_residual_survives_a_poisoned_round():
    """One non-finite round must not poison the residual chain forever:
    the NaN ships (and dies at the server gate) but the residual update
    is skipped, so the next honest round resumes from the pre-poison
    residual."""
    rs = np.random.RandomState(5)
    delta = [rs.randn(8, 4).astype(np.float32)]
    ef = ErrorFeedback()
    comp = ef.compensate(delta)
    payload, scales = encode_update(comp, "delta-int8")
    ef.update(comp, decode_update(payload, scales, "delta-int8", delta))
    pre = [r.copy() for r in ef.residual]
    poisoned = [np.full((8, 4), np.nan, np.float32)]
    comp_bad = ef.compensate(poisoned)
    pb, sb = encode_update(comp_bad, "delta-int8")
    ef.update(comp_bad, decode_update(pb, sb, "delta-int8", poisoned))
    np.testing.assert_array_equal(ef.residual[0], pre[0])  # kept, not NaN
    assert np.isfinite(ef.compensate(delta)[0]).all()


def test_rank_recovers_after_adversary_window_under_quantized_tier(lr_setup):
    """End to end: a NaN adversary active only in rounds [0, 2) under
    delta-int8 — the rank is quarantined during the window and RECOVERS
    after it (the EF residual was not poisoned); the job converges."""
    from fedml_tpu.chaos import AdversaryPlan
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    plan = AdversaryPlan.from_json({"seed": 2, "rules": [
        {"attack": "nan", "ranks": [2], "rounds": [0, 2]}]})
    agg = run_simulated(data, task, _cfg(rounds=6), backend="LOOPBACK",
                        job_id="t-nan-window", update_codec="delta-int8",
                        adversary_plan=plan)
    rounds_hit = {e[0] for e in agg.quarantine.canonical()}
    assert rounds_hit and rounds_hit <= {0, 1}, \
        f"quarantines outside the adversary window: {rounds_hit}"
    assert agg.history[-1]["test_acc"] > 0.9, agg.history[-1]


def test_structural_garbage_raises_corrupt_payload():
    """Truncated deflate streams, wrong leaf counts, and short sign
    payloads raise CorruptPayload (the server maps it to an 'undecodable'
    quarantine); a corrupt SCALE is value garbage — it decodes to values
    the sanitation gate judges instead."""
    d = [np.ones((8, 8), np.float32)]
    payload, scales = encode_update(d, "delta-int8")
    with pytest.raises(CorruptPayload):
        decode_update([payload[0][:3]], scales, "delta-int8", d)
    with pytest.raises(CorruptPayload):
        decode_update(payload, scales, "delta-int8",
                      d + [np.ones(2, np.float32)])
    sp, ss = encode_update(d, "delta-sign1")
    with pytest.raises(CorruptPayload):
        decode_update([sp[0][:1]], ss, "delta-sign1", d)
    # corrupt scale: decodes (no raise), non-finite for the gate
    bad = decode_update(payload, np.array([np.nan], np.float32),
                        "delta-int8", d)[0]
    assert not np.isfinite(bad).any()


# ------------------------------------------------- frame-codec exemptions
def test_codec_payloads_exempt_from_lossy_frame_tiers():
    """Satellite: sparse/update payloads must ride the frame VERBATIM
    under the lossy f16/q8 tiers — a quantized sparse_val breaks the
    client's EF accounting, a quantized upd_scale corrupts every entry it
    scales. mark_lossless extends the exemption per message (the
    delta-broadcast dense fallback)."""
    rs = np.random.RandomState(3)
    vals = [rs.randn(64).astype(np.float32)]
    idx = [np.arange(64, dtype=np.int32)]
    scales = np.array([0.123, np.nan], np.float32)
    q = [np.arange(32, dtype=np.uint8)]
    model = [rs.randn(8, 8).astype(np.float32)]
    for codec in ("q8", "f16", "q8+zlib"):
        m = Message("c2s_send_model", 1, 0)
        m.add_params("sparse_idx", idx)
        m.add_params("sparse_val", vals)
        m.add_params("upd_q", q)
        m.add_params("upd_scale", scales)
        m.add_params("model_params", model)
        r = Message.from_bytes(m.to_bytes(codec))
        np.testing.assert_array_equal(r.get("sparse_idx")[0], idx[0])
        np.testing.assert_array_equal(r.get("sparse_val")[0], vals[0])
        np.testing.assert_array_equal(r.get("upd_q")[0], q[0])
        np.testing.assert_array_equal(r.get("upd_scale"), scales)
        # model_params NOT exempt by default: the lossy tier transformed it
        assert not np.array_equal(r.get("model_params")[0], model[0])
        m2 = Message("s2c_sync", 0, 1)
        m2.add_params("model_params", model)
        m2.mark_lossless("model_params")
        r2 = Message.from_bytes(m2.to_bytes(codec))
        np.testing.assert_array_equal(r2.get("model_params")[0], model[0])


def test_q8_frame_codec_with_sparsify_regression(lr_setup):
    """--compression q8 + --sparsify_ratio: the lossy frame tier must not
    touch the sparse payload (it used to ride whatever codec was set) —
    the run completes and learns with EF intact."""
    from fedml_tpu.comm.message import set_wire_codec
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    set_wire_codec("q8")
    try:
        agg = run_simulated(data, task, _cfg(rounds=6), backend="LOOPBACK",
                            job_id="t-q8-sparse", sparsify_ratio=0.5)
    finally:
        set_wire_codec("none")
    assert agg.history[-1]["round"] == 5
    assert agg.history[-1]["test_acc"] > 0.9, agg.history[-1]


# --------------------------------------------------- end-to-end parities
def test_delta_uplink_lossless_matches_standalone(lr_setup):
    """update_codec='delta' ships local - global@version verbatim: the
    distributed run equals the standalone engine at the dense oracle's
    tolerance (a + (b - a) carries only f32 roundoff)."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    cfg = _cfg()
    standalone = FedAvgAPI(data, task, cfg)
    standalone.train()
    agg = run_simulated(data, task, cfg, backend="LOOPBACK",
                        job_id="t-delta-lossless", update_codec="delta")
    for a, b in zip(pack_pytree(standalone.net), pack_pytree(agg.net)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_delta_broadcast_matches_dense_and_survives_cold_ranks(lr_setup):
    """Round-delta downlink: warm ranks reconstruct global@r = held +
    delta bit-for-bit along the server's chain, so the run equals the
    standalone engine like the dense broadcast does; under a chaos-dropped
    downlink the missed rank's next broadcast falls back to DENSE (proof-
    based warm tracking self-heals) and the job still completes."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    cfg = _cfg()
    standalone = FedAvgAPI(data, task, cfg)
    standalone.train()
    agg = run_simulated(data, task, cfg, backend="LOOPBACK",
                        job_id="t-delta-bcast", delta_broadcast=True)
    for a, b in zip(pack_pytree(standalone.net), pack_pytree(agg.net)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    # cold-rank fallback: rank 2 misses round 1's downlink entirely
    plan = FaultPlan.from_json({"seed": 4, "rules": [
        {"fault": "drop", "direction": "send", "src": [0], "dst": [2],
         "rounds": [1, 2]}]})
    agg = run_simulated(data, task, _cfg(rounds=4), backend="LOOPBACK",
                        job_id="t-delta-bcast-cold", delta_broadcast=True,
                        chaos_plan=plan, round_timeout_s=1.0)
    assert agg.history[-1]["round"] == 3
    assert agg.history[-1]["test_acc"] > 0.9, agg.history[-1]


def test_quantized_tiers_converge_with_ef_and_degrade_without(lr_setup):
    """Acceptance: EF keeps the lossy tiers within the dense run's final
    loss ballpark at matched rounds, and the SAME tier without EF is
    visibly worse — the residual is what preserves convergence, not the
    quantizer."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    cfg = _cfg(rounds=8)

    def final_loss(job, **kw):
        agg = run_simulated(data, task, cfg, backend="LOOPBACK",
                            job_id=job, **kw)
        assert agg.history[-1]["round"] == cfg.comm_round - 1
        return float(agg.history[-1]["test_loss"])

    dense = final_loss("t-ef-dense")
    for tier in ("delta-int8", "delta-sign1"):
        ef = final_loss(f"t-ef-{tier}", update_codec=tier)
        noef = final_loss(f"t-noef-{tier}", update_codec=tier,
                          error_feedback=False)
        assert ef <= dense + 0.02, (tier, ef, dense)
        assert noef >= 1.5 * ef, \
            f"{tier}: no-EF loss {noef} not visibly worse than EF {ef}"


def test_nan_upload_quarantined_under_quantized_tiers(lr_setup):
    """Acceptance: quantized garbage quarantines at the PR-4 gate — a NaN
    client under delta-int8/sign1 encodes to a NaN scale, decodes
    non-finite, and dies at the gate; the aggregate stays finite and the
    job completes."""
    from fedml_tpu.chaos import AdversaryPlan
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    for tier in ("delta-int8", "delta-sign1"):
        plan = AdversaryPlan.from_json(
            {"seed": 1, "rules": [{"attack": "nan", "ranks": [2]}]})
        agg = run_simulated(data, task, _cfg(), backend="LOOPBACK",
                            job_id=f"t-nan-{tier}", update_codec=tier,
                            adversary_plan=plan)
        led = agg.quarantine.canonical()
        assert led and any(e[1] == 2 for e in led), led
        assert agg.quarantine.counts().get("nonfinite", 0) > 0
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in pack_pytree(agg.net))
        assert agg.history[-1]["round"] == 2


# ----------------------------------------------- server decode hardening
def _partial_server(version_pack):
    """A server manager shell exercising _decode_upload without the comm
    stack (the test_dead_rank_same_round_resend_skipped pattern)."""
    from fedml_tpu.core.robust_agg import QuarantineLedger
    from fedml_tpu.distributed.fedavg.server_manager import (
        FedAvgServerManager,
    )

    mgr = object.__new__(FedAvgServerManager)
    mgr.round_idx = 1
    mgr._version_pack = version_pack
    mgr._staleness_bound = None
    mgr.aggregator = types.SimpleNamespace(quarantine=QuarantineLedger())
    return mgr


def test_server_quarantines_undecodable_payloads():
    """A structurally-garbage payload that survived CRC (chaos bit flip)
    costs ONE upload — quarantined with reason 'undecodable', counted,
    never a crashed receive loop."""
    from fedml_tpu.distributed.fedavg.message_define import MyMessage

    base = [np.zeros((4, 4), np.float32)]
    mgr = _partial_server({1: base})
    payload, scales = encode_update([np.ones((4, 4), np.float32)],
                                    "delta-int8")
    msg = {MyMessage.MSG_ARG_KEY_UPDATE_CODEC: "delta-int8",
           MyMessage.MSG_ARG_KEY_UPDATE_PAYLOAD: [payload[0][:2]],
           MyMessage.MSG_ARG_KEY_UPDATE_SCALE: scales}
    assert mgr._decode_upload(msg, 3, 1) is None
    led = mgr.aggregator.quarantine.canonical()
    assert led and led[0][1] == 3 and led[0][2] == "undecodable", led
    # an intact payload through the same path decodes fine
    msg[MyMessage.MSG_ARG_KEY_UPDATE_PAYLOAD] = payload
    out = mgr._decode_upload(msg, 3, 1)
    assert out is not None and np.isfinite(out[0]).all()


def test_server_quarantines_corrupt_sparse_payloads():
    """Sparse-tier structural garbage: an out-of-range top-k index (bit
    flip surviving CRC — IndexError in the scatter) and a leaf-count
    mismatch both quarantine as 'undecodable', never crash the loop."""
    from fedml_tpu.distributed.fedavg.message_define import MyMessage

    base = [np.zeros(8, np.float32)]
    mgr = _partial_server({1: base})
    msg = {MyMessage.MSG_ARG_KEY_SPARSE_IDX: [np.array([99], np.int32)],
           MyMessage.MSG_ARG_KEY_SPARSE_VAL: [np.array([1.0], np.float32)]}
    assert mgr._decode_upload(msg, 2, 1) is None
    msg = {MyMessage.MSG_ARG_KEY_SPARSE_IDX: [np.array([0], np.int32)] * 2,
           MyMessage.MSG_ARG_KEY_SPARSE_VAL: [np.array([1.0], np.float32)] * 2}
    assert mgr._decode_upload(msg, 2, 1) is None
    assert [e[2] for e in mgr.aggregator.quarantine.canonical()] == \
        ["undecodable", "undecodable"]


def test_aggregate_with_no_decodable_uploads_keeps_global():
    """An all-undecodable round must keep the current global model, not
    crash on an empty slot table (the barrier is satisfied by arrivals,
    decodable or not — server_manager marks the flag either way)."""
    from fedml_tpu.core.robust_agg import QuarantineLedger
    from fedml_tpu.distributed.fedavg.aggregator import FedAvgAggregator

    agg = object.__new__(FedAvgAggregator)
    agg.model_dict, agg.sample_num_dict = {}, {}
    agg.current_round = 0
    agg.quarantine = QuarantineLedger()
    agg.net = {"w": np.ones(3, np.float32)}
    agg._aggregate_core()  # must not raise
    np.testing.assert_array_equal(agg.net["w"], np.ones(3, np.float32))


def test_genuinely_unversioned_base_is_loud():
    """An encoded upload naming a version the server never broadcast is a
    protocol bug, not wire damage — RuntimeError, never a silent drop."""
    from fedml_tpu.distributed.fedavg.message_define import MyMessage

    mgr = _partial_server({1: [np.zeros(3, np.float32)]})
    payload, scales = encode_update([np.ones(3, np.float32)], "delta-int8")
    msg = {MyMessage.MSG_ARG_KEY_UPDATE_CODEC: "delta-int8",
           MyMessage.MSG_ARG_KEY_UPDATE_PAYLOAD: payload,
           MyMessage.MSG_ARG_KEY_UPDATE_SCALE: scales}
    with pytest.raises(RuntimeError, match="versioned base"):
        mgr._decode_upload(msg, 2, 7)


def test_client_manager_validates_update_codec():
    from fedml_tpu.distributed.fedavg.client_manager import (
        FedAvgClientManager,
    )

    with pytest.raises(ValueError, match="update_codec"):
        FedAvgClientManager(None, rank=1, size=2, backend="LOOPBACK",
                            update_codec="int7", job_id="t-badcodec")
    with pytest.raises(ValueError, match="mutually exclusive"):
        FedAvgClientManager(None, rank=1, size=2, backend="LOOPBACK",
                            update_codec="delta-int8", sparsify_ratio=0.5,
                            job_id="t-bothtiers")


# ------------------------------------------------- async-waves composition
def test_async_buffered_composes_with_encoded_uplinks(lr_setup):
    """Satellite: the PR-8 dense-uploads-only refusal is lifted — top-k
    and quantized uplinks encode against the version the dispatch wave
    carried and densify against the server's per-version stash, so
    buffered-async runs complete and converge with sparse/quantized
    uploads."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    cfg = _cfg(rounds=6)
    for job, kw in (("t-async-topk", {"sparsify_ratio": 0.5}),
                    ("t-async-int8", {"update_codec": "delta-int8"})):
        agg = run_simulated(data, task, cfg, backend="LOOPBACK", job_id=job,
                            async_buffer_k=2, staleness="poly:0.5",
                            buffer_deadline_s=2.0, **kw)
        assert agg.history[-1]["round"] == cfg.comm_round - 1
        assert agg.history[-1]["test_acc"] > 0.9, (job, agg.history[-1])


# ---------------------------------------------------- chaos replay per tier
@pytest.mark.parametrize("tier_kw", [
    {"update_codec": "delta"},
    {"update_codec": "delta-int8"},
    {"update_codec": "delta-sign1"},
    {"sparsify_ratio": 0.3},
], ids=["delta", "delta-int8", "delta-sign1", "topk"])
def test_chaos_replay_bitwise_per_tier(lr_setup, tier_kw):
    """Acceptance: every codec tier replays bit-for-bit under a seeded
    chaos plan — identical fault ledgers AND identical final models (the
    EF residual chain and the quantizers are deterministic)."""
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    spec = {"seed": 7, "rules": [
        {"fault": "drop", "direction": "send", "src": [2], "dst": [0],
         "rounds": [1, 2]},
        {"fault": "corrupt", "direction": "recv", "src": [1], "dst": [0],
         "prob": 0.5},
        {"fault": "duplicate", "direction": "send", "src": [3], "dst": [0]},
    ]}
    runs = []
    for i in range(2):
        plan = FaultPlan.from_json(spec)
        agg = run_simulated(data, task, _cfg(), backend="LOOPBACK",
                            job_id=f"t-tier-rep-{i}", chaos_plan=plan,
                            round_timeout_s=1.0, **tier_kw)
        assert agg.history[-1]["round"] == 2
        runs.append((plan.ledger.canonical(), agg.quarantine.canonical(),
                     [np.asarray(v) for v in pack_pytree(agg.net)]))
    assert runs[0][0] == runs[1][0] and len(runs[0][0]) > 0
    assert runs[0][1] == runs[1][1]
    for a, b in zip(runs[0][2], runs[1][2]):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------ byte budget + accounting
def test_uplink_byte_reduction_floors():
    """Acceptance floors on actual wire bytes (comm_bytes_total deltas,
    full frames including headers): delta-int8 >= 8x and delta-sign1 >=
    25x below the dense f32 protocol at matched rounds, on a model large
    enough that headers don't dominate (~16k params)."""
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs.comm_instrument import directional_bytes

    data = synthetic_images(num_clients=8, image_shape=(40, 40, 1),
                            num_classes=10, samples_per_client=24,
                            test_samples=96, seed=3)
    task = classification_task(LogisticRegression(num_classes=10))
    cfg = _cfg(rounds=3, per_round=2, lr=0.05)

    def uplink(job, **kw):
        before = directional_bytes()["uplink"]
        agg = run_simulated(data, task, cfg, backend="LOOPBACK",
                            job_id=job, **kw)
        assert agg.history[-1]["round"] == cfg.comm_round - 1
        return directional_bytes()["uplink"] - before

    dense = uplink("t-bytes-dense")
    int8 = uplink("t-bytes-int8", update_codec="delta-int8")
    sign = uplink("t-bytes-sign1", update_codec="delta-sign1")
    assert dense / int8 >= 8.0, f"int8 reduction {dense / int8:.1f}x < 8x"
    assert dense / sign >= 25.0, f"sign1 reduction {dense / sign:.1f}x < 25x"


def test_comm_bytes_direction_split_and_report_columns(lr_setup):
    """comm_bytes_total{codec,direction} splits uplink from downlink (one
    undirected counter hid that broadcast dominates downlink); report.py
    renders tx_up_B/tx_down_B and hides them on pre-PR-9 logs."""
    import os
    import sys

    from fedml_tpu.distributed.fedavg import run_simulated
    from fedml_tpu.obs.comm_instrument import directional_bytes
    from fedml_tpu.obs.metrics import REGISTRY

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import report

    data, task = lr_setup
    before = directional_bytes()
    run_simulated(data, task, _cfg(rounds=2, per_round=2), backend="LOOPBACK",
                  job_id="t-dirbytes", update_codec="delta-int8")
    after = directional_bytes()
    assert after["uplink"] > before["uplink"]
    assert after["downlink"] > before["downlink"]
    # dense downlink vs quantized uplink: downlink must dominate
    assert (after["downlink"] - before["downlink"]) > \
        (after["uplink"] - before["uplink"])
    # the effective-codec label separates the quantized tier from dense
    snap = REGISTRY.snapshot().get("comm_bytes_total", {})
    assert any("codec=delta-int8" in k for k in snap), sorted(snap)
    # report.py: new logs show the columns, old logs hide them
    new = [{"kind": "round", "round": 0, "comm": {
        "messages_sent": 4, "bytes_sent": 100,
        "bytes_uplink": 60.0, "bytes_downlink": 40.0}}]
    old = [{"kind": "round", "round": 0,
            "comm": {"messages_sent": 4, "bytes_sent": 100}}]
    assert "tx_up_B" in report.render_table(new)
    assert "tx_down_B" in report.render_table(new)
    assert "tx_up_B" not in report.render_table(old)


def test_shed_vocab_pinned_to_perf_instrument():
    """perf_instrument pre-registers the shed families from an inlined
    copy of SHED_REASONS (obs must not import core) — pin the mirror so
    the vocabularies cannot drift."""
    from fedml_tpu.core.async_buffer import SHED_REASONS
    from fedml_tpu.obs.metrics import REGISTRY
    from fedml_tpu.obs.perf_instrument import ensure_async_shed_families

    ensure_async_shed_families()
    fam = REGISTRY.snapshot().get("fed_async_shed_total", {})
    for reason in SHED_REASONS:
        assert f"reason={reason}" in fam, (reason, sorted(fam))
    # the quarantine-ledger vocabulary, pinned alongside: the ledger-only
    # reasons (no in-graph code) every runtime's ledger may carry —
    # 'undecodable' (PR-9 wire tiers), 'edge_lost' (cross-tier elastic
    # edge loss, docs/ROBUSTNESS.md §Cross-tier robust gating), the
    # masked-secure-aggregation pair 'secagg_dropout'/'secagg_shed'
    # (§Secure aggregation dropout recovery / below-threshold shed), and
    # 'server_restart' (uploads accepted-then-lost to a server crash,
    # §Server crash recovery)
    from fedml_tpu.core.robust_agg import REASONS

    assert REASONS == ("ok", "nonfinite", "norm_outlier", "suspected",
                       "undecodable", "edge_lost", "secagg_dropout",
                       "secagg_shed", "server_restart")
