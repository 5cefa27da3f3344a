"""Hierarchical masked secure aggregation (docs/ROBUSTNESS.md
§Hierarchical secure aggregation): pairwise masks drawn within each edge
block cancel AT THE EDGE, every edge forwards one unmasked mod-p field
partial, and the root decodes once — so the tree is bitwise the flat
masked run (mod-p addition is exact and associative), including under
in-block dropout recovered by the edge-local tiered reveal.

Acceptance battery:
- clean tree ≡ flat: model bits AND ledger, host fold and fused ingest;
- in-block dropout: the edge-local reveal strips the dead slot's masks
  and tree ≡ flat stays bitwise (model bits AND quarantine ledger);
- steady-state root ingress is O(edges) frames (fanin_history pinned);
- a crashed EDGE sheds exactly its block's slots (``secagg_shed``), the
  other blocks' round proceeds, and the whole schedule replays
  bit-for-bit;
- reveal-frame loss at either tier is healed by the watchdog's
  deterministic retry (deduped at the receiver) — the job completes and
  replays bit-for-bit.
"""

import numpy as np
import pytest

# ------------------------------------------------------------------ fixtures


@pytest.fixture(scope="module")
def lr_setup():
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.linear import LogisticRegression

    data = synthetic_images(num_clients=8, image_shape=(6, 6, 1),
                            num_classes=3, samples_per_client=12,
                            test_samples=24, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    return data, task


def _cfg(rounds=2, per_round=8, seed=0, **kw):
    from fedml_tpu.algorithms.fedavg import FedAvgConfig

    return FedAvgConfig(comm_round=rounds, client_num_in_total=8,
                        client_num_per_round=per_round, epochs=1,
                        batch_size=6, lr=0.1, frequency_of_the_test=1,
                        seed=seed, **kw)


def _params_equal(a, b):
    import jax

    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------ clean bitwise
def test_tree_matches_flat_bitwise_clean(lr_setup):
    """Tree ≡ flat on a clean full-cohort run — model bits, ledger, and
    history length — for both the host fold and the device-resident
    fused ingest; root ingress is exactly E frames per round."""
    from fedml_tpu.distributed import turboaggregate as ta

    data, task = lr_setup
    rounds = 2
    flat = ta.run_simulated(data, task, _cfg(rounds=rounds),
                            job_id="t-hsa-flat")
    tree = ta.run_simulated(data, task, _cfg(rounds=rounds),
                            job_id="t-hsa-tree", edges=2)
    fused = ta.run_simulated(data, task, _cfg(rounds=rounds),
                             job_id="t-hsa-tree-fused", edges=2,
                             fused_ingest=True)
    _params_equal(flat.net.params, tree.net.params)
    _params_equal(flat.net.params, fused.net.params)
    assert tree.quarantine.canonical() == []
    assert flat.quarantine.canonical() == []
    assert tree.fanin_history == [2] * rounds  # O(edges) update ingress
    assert tree.history and tree.history[-1]["round"] == rounds - 1


def test_tree_round_records_carry_hier_and_secagg_blocks(lr_setup,
                                                         tmp_path):
    from fedml_tpu.distributed import turboaggregate as ta
    from fedml_tpu.obs import Telemetry
    from fedml_tpu.obs.events import read_jsonl

    data, task = lr_setup
    tel = Telemetry(log_dir=str(tmp_path))
    ta.run_simulated(data, task, _cfg(rounds=2), job_id="t-hsa-rec",
                     edges=2, telemetry=tel)
    tel.close()
    recs = [r for r in read_jsonl(str(tmp_path / "events.jsonl"))
            if r.get("kind") == "round"]
    assert len(recs) == 2
    for r in recs:
        assert r["hier"]["edges"] == 2 and r["hier"]["block"] == 4
        assert r["hier"]["fan_in"] == 2
        assert r["secagg"]["outcome"] == "full"


# ------------------------------------------------------- in-block dropout
def test_tree_matches_flat_bitwise_with_inblock_dropout(lr_setup):
    """The tentpole equivalence: one slot crashed inside the round
    deadline. Flat recovers via the root-coordinated reveal, the tree
    via the EDGE-LOCAL reveal — and because both decode the identical
    survivor field sum, model bits AND the quarantine ledger agree
    bitwise. Root ingress stays O(edges) even through recovery."""
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed import turboaggregate as ta
    from fedml_tpu.obs.metrics import REGISTRY

    data, task = lr_setup
    rounds = 3
    # cohort slot 1 dark for rounds 1-2: flat wire rank 2, tree wire
    # rank 4 (worker ranks shift past the two edge ranks)
    flat_plan = FaultPlan.from_json({"seed": 7, "rules": [
        {"fault": "crash", "ranks": [2], "rounds": [1, 3]}]})
    tree_plan = lambda: FaultPlan.from_json({"seed": 7, "rules": [  # noqa: E731
        {"fault": "crash", "ranks": [4], "rounds": [1, 3]}]})
    before = REGISTRY.snapshot().get("fed_secagg_rounds_total", {})
    flat = ta.run_simulated(data, task, _cfg(rounds=rounds),
                            job_id="t-hsa-drop-flat",
                            chaos_plan=flat_plan, round_timeout_s=2.0)
    tree = ta.run_simulated(data, task, _cfg(rounds=rounds),
                            job_id="t-hsa-drop-tree", edges=2,
                            chaos_plan=tree_plan(), round_timeout_s=2.0)
    _params_equal(flat.net.params, tree.net.params)
    led = tree.quarantine.canonical()
    assert led == flat.quarantine.canonical()
    # slot 1 (cohort rank 2) attributed secagg_dropout on the crash window
    drops = [e for e in led if e[2] == "secagg_dropout"]
    assert drops and {e[1] for e in drops} == {2}, led
    assert {e[0] for e in drops} == {1, 2}, led
    after = REGISTRY.snapshot().get("fed_secagg_rounds_total", {})
    assert after.get("outcome=recovered", 0) > before.get(
        "outcome=recovered", 0)
    # O(edges): the recovered rounds still reached the root as E frames
    assert tree.fanin_history == [2] * rounds

    # the whole schedule replays bit-for-bit
    again = ta.run_simulated(data, task, _cfg(rounds=rounds),
                             job_id="t-hsa-drop-replay", edges=2,
                             chaos_plan=tree_plan(), round_timeout_s=2.0)
    assert again.quarantine.canonical() == led
    _params_equal(tree.net.params, again.net.params)


# ------------------------------------------------------------- edge crash
def test_edge_crash_sheds_exactly_its_block_and_replays(lr_setup):
    """A whole edge lost inside round_timeout_s: the root sheds EXACTLY
    that block's slots (``secagg_shed``, client-attributed), the other
    block's partial folds normally, and the schedule replays
    bit-for-bit. No cross-block mask ever needs repair — the other
    edge's partial arrived already unmasked."""
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed import turboaggregate as ta
    from fedml_tpu.obs.metrics import REGISTRY

    data, task = lr_setup
    rounds = 3
    plan = lambda: FaultPlan.from_json({"seed": 9, "rules": [  # noqa: E731
        {"fault": "crash", "ranks": [1], "rounds": [1, 2]}]})
    before = REGISTRY.snapshot().get("fed_secagg_rounds_total", {})
    tree = ta.run_simulated(data, task, _cfg(rounds=rounds),
                            job_id="t-hsa-edgecrash", edges=2,
                            chaos_plan=plan(), round_timeout_s=2.0)
    led = tree.quarantine.canonical()
    sheds = [e for e in led if e[2] == "secagg_shed"]
    # block 0 = slots 0-3 = cohort ranks 1-4 — and ONLY that block
    assert sheds and {e[1] for e in sheds} <= {1, 2, 3, 4}, led
    assert any(e[0] == 1 for e in sheds), led
    assert not [e for e in led if e[1] > 4], led
    after = REGISTRY.snapshot().get("fed_secagg_rounds_total", {})
    assert after.get("outcome=shed", 0) > before.get("outcome=shed", 0)
    assert tree.history and tree.history[-1]["round"] == rounds - 1

    again = ta.run_simulated(data, task, _cfg(rounds=rounds),
                             job_id="t-hsa-edgecrash-replay", edges=2,
                             chaos_plan=plan(), round_timeout_s=2.0)
    assert again.quarantine.canonical() == led
    _params_equal(tree.net.params, again.net.params)


# ------------------------------------------------------ reveal hardening
def test_reveal_frames_survive_lossy_links_flat(lr_setup):
    """Satellite hardening, flat tier: seeded probabilistic drops on a
    survivor's uplink (which carries its c2s_reveal replies) are healed
    by the watchdog's deterministic reveal retry — the job completes
    every round and the run replays bit-for-bit."""
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed import turboaggregate as ta

    data, task = lr_setup
    chaos = lambda: FaultPlan.from_json({"seed": 13, "rules": [  # noqa: E731
        {"fault": "crash", "ranks": [2], "rounds": [1, 3]},
        {"fault": "drop", "direction": "send", "src": [3], "dst": [0],
         "prob": 0.4, "rounds": [1, 3]}]})
    # run 0 is a warm-up and is not compared: while the first jit of a
    # process compiles, round 0 outlasts round_timeout_s, the watchdog
    # re-broadcasts, and the link sequence numbers the seeded drops are
    # keyed on shift. The schedule has to hang on the fault plan, not on
    # compile time, so the two runs compared both find their programs built
    runs = []
    for i in range(3):
        agg = ta.run_simulated(data, task, _cfg(rounds=3),
                               job_id=f"t-hsa-lossy-flat-{i}",
                               chaos_plan=chaos(), round_timeout_s=2.0)
        assert agg.history[-1]["round"] == 2
        runs.append((agg.net.params, agg.quarantine.canonical()))
    assert runs[1][1] == runs[2][1]
    _params_equal(runs[1][0], runs[2][0])


def test_reveal_frames_survive_lossy_links_tree(lr_setup):
    """Satellite hardening, edge tier: with slot 1 crashed, seeded drops
    on a surviving worker's uplink to its edge lose reveal replies; the
    edge watchdog's retry (then, past it, the block shed) keeps the job
    live and the schedule deterministic."""
    from fedml_tpu.chaos import FaultPlan
    from fedml_tpu.distributed import turboaggregate as ta

    data, task = lr_setup
    chaos = lambda: FaultPlan.from_json({"seed": 17, "rules": [  # noqa: E731
        {"fault": "crash", "ranks": [4], "rounds": [1, 3]},
        {"fault": "drop", "direction": "send", "src": [3], "dst": [1],
         "prob": 0.4, "rounds": [1, 3]}]})
    # run 0 is a warm-up and is not compared (see the flat twin above).
    # 5 s, not the flat twin's 2: with six test workers on a shared host a
    # live worker's round has outlasted 2 s warm too (the driver's run of
    # PR 32), and a re-broadcast shifts the schedule as a compile does
    runs = []
    for i in range(3):
        agg = ta.run_simulated(data, task, _cfg(rounds=3),
                               job_id=f"t-hsa-lossy-tree-{i}", edges=2,
                               chaos_plan=chaos(), round_timeout_s=5.0)
        assert agg.history[-1]["round"] == 2
        assert agg.fanin_history and len(agg.fanin_history) == 3
        runs.append((agg.net.params, agg.quarantine.canonical()))
    assert runs[1][1] == runs[2][1]
    _params_equal(runs[1][0], runs[2][0])


def test_client_reveal_cache_retransmits_verbatim(lr_setup):
    """The receiver-side dedup (satellite hardening): a retried reveal
    request that finds the reveal already computed retransmits the
    cached reply VERBATIM — the trainer derives the seeds exactly once
    per (round, dead-set)."""
    from fedml_tpu.distributed.fedavg.message_define import MyMessage
    from fedml_tpu.distributed.turboaggregate import (
        SecureTrainer,
        TASecureClientManager,
    )

    data, task = lr_setup
    trainer = SecureTrainer(3, data, task, _cfg(per_round=5))
    mgr = TASecureClientManager(trainer, rank=3, size=6,
                                backend="LOOPBACK", job_id="t-hsa-cache")
    try:
        sent = []
        mgr.send_message = lambda m: sent.append(m)
        calls = []
        real = trainer.reveal_pair_seeds
        trainer.reveal_pair_seeds = lambda r, d: (
            calls.append((r, tuple(d))) or real(r, d))
        req = {MyMessage.MSG_ARG_KEY_ROUND: 1,
               MyMessage.MSG_ARG_KEY_SECAGG_DEAD: np.asarray([0, 4])}
        mgr.handle_message_reveal_request(dict(req))
        mgr.handle_message_reveal_request(dict(req))
        assert len(calls) == 1  # the retry hit the cache
        assert len(sent) == 2
        a, b = (m.get_params() for m in sent)
        for key in (MyMessage.MSG_ARG_KEY_SECAGG_DEAD,
                    MyMessage.MSG_ARG_KEY_SECAGG_PAIR_SEEDS):
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(b[key]))
        # a NEW dead-set recomputes (and evicts the stale entry)
        req2 = {MyMessage.MSG_ARG_KEY_ROUND: 1,
                MyMessage.MSG_ARG_KEY_SECAGG_DEAD: np.asarray([4])}
        mgr.handle_message_reveal_request(req2)
        assert len(calls) == 2 and calls[-1] == (1, (4,))
        assert list(mgr._reveal_cache) == [(1, (4,))]
    finally:
        mgr.finish()
