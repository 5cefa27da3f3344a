"""Server crash tolerance (docs/ROBUSTNESS.md §Server crash recovery):
durable round WAL + supervised restart + client session resumption,
driven end-to-end through chaos ``crash`` rules naming rank 0 — the
loopback supervision driver kills the server manager at the scheduled
point (no farewell frames, no graceful saves) and boots a fresh one
through the real checkpoint + WAL recovery path while the CLIENTS RUN
ON, surviving the outage and answering the resume probe.

Acceptance battery:
- crash BETWEEN round commits -> final model AND quarantine ledger
  bitwise ≡ the uninterrupted run (sync, and DP including cumulative ε);
- crash MID-ROUND -> the run completes, every accepted-then-lost upload
  is ledgered ``server_restart`` slot-exact, the re-run round folds
  sample-weight exact (with a simultaneously crashed client: the exact
  elastic partial, bitwise the client-crash-only oracle);
- a DP run killed mid-round never reports a LOWER cumulative ε than the
  charges incurred (WAL pre-charge fsync'd before the noise draw,
  replayed at recovery);
- a secagg server crash mid-REVEAL sheds (``secagg_shed`` ledgered) and
  the retry is bitwise-clean — never a half-recovered fold;
- restart observability: fed_server_restarts_total / fed_restart_epoch /
  recovery seconds, the restart_storm health rule, /healthz
  restart_epoch, report.py's ``restarts`` column (hidden on old logs).
"""

import os
import tempfile

import numpy as np
import pytest

from fedml_tpu.chaos import FaultPlan, FaultRule
from fedml_tpu.comm.message import pack_pytree
from fedml_tpu.obs.metrics import REGISTRY


@pytest.fixture(scope="module")
def lr_setup():
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.linear import LogisticRegression

    data = synthetic_images(num_clients=8, image_shape=(8, 8, 1),
                            num_classes=4, samples_per_client=24,
                            test_samples=96, seed=3)
    task = classification_task(LogisticRegression(num_classes=4))
    return data, task


def _cfg(rounds=4, per_round=3, **kw):
    from fedml_tpu.algorithms.fedavg import FedAvgConfig

    base = dict(client_num_in_total=8, client_num_per_round=per_round,
                epochs=1, batch_size=8, lr=0.1, frequency_of_the_test=1,
                seed=0)
    base.update(kw)
    return FedAvgConfig(comm_round=rounds, **base)


def _crash_plan(round_idx, after_uploads=None, extra_rules=()):
    rule = {"fault": "crash", "ranks": [0],
            "rounds": [round_idx, round_idx + 1]}
    if after_uploads is not None:
        rule["after_uploads"] = after_uploads
    return FaultPlan.from_json({"seed": 1,
                                "rules": [rule, *extra_rules]})


def _assert_bitwise(a_net, b_net):
    for a, b in zip(pack_pytree(a_net), pack_pytree(b_net)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- plan validation
def test_rank0_crash_rule_schema():
    # a server-crash rule must be windowed (an unbounded window would
    # re-kill the recovered server forever) and after_uploads is
    # crash-only; the schedule round-trips through JSON
    with pytest.raises(ValueError, match="rounds"):
        FaultRule(fault="crash", ranks=[0])
    with pytest.raises(ValueError, match="after_uploads"):
        FaultRule(fault="drop", after_uploads=2)
    plan = FaultPlan.from_json({"seed": 3, "rules": [
        {"fault": "crash", "ranks": [0], "rounds": [2, 3],
         "after_uploads": 1},
        {"fault": "crash", "ranks": [0], "rounds": [1, 2]},
        {"fault": "crash", "ranks": [3], "rounds": [1, 2]}]})
    assert plan.server_crash_points() == [(1, None), (2, 1)]
    again = FaultPlan.from_json(plan.to_json())
    assert again.server_crash_points() == plan.server_crash_points()
    # a between-commits and a mid-round kill in the SAME round is a valid
    # schedule (None sorts first, no None-vs-int TypeError), and anything
    # below -1 can never fire so it is rejected at construction
    mixed = FaultPlan.from_json({"seed": 0, "rules": [
        {"fault": "crash", "ranks": [0], "rounds": [2, 3],
         "after_uploads": 1},
        {"fault": "crash", "ranks": [0], "rounds": [2, 3]}]})
    assert mixed.server_crash_points() == [(2, None), (2, 1)]
    with pytest.raises(ValueError, match="after_uploads"):
        FaultRule(fault="crash", ranks=[0], rounds=[1, 2],
                  after_uploads=-2)
    # the driver needs a durable recovery substrate
    from fedml_tpu.distributed.fedavg import run_simulated

    with pytest.raises(ValueError, match="ckpt_dir"):
        run_simulated(None, None, _cfg(), chaos_plan=plan)


# ------------------------------------------------------- sync crash battery
def test_between_commits_crash_bitwise(lr_setup, tmp_path):
    """Seeded rank-0 crash between round commits: supervised restart ->
    final model AND quarantine ledger bitwise ≡ the uninterrupted run
    (the headline acceptance criterion)."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    oracle = run_simulated(data, task, _cfg(), job_id="t-sc-oracle",
                           round_timeout_s=2.0)
    before = REGISTRY.total("fed_server_restarts_total")
    crashed = run_simulated(data, task, _cfg(), job_id="t-sc-bc",
                            chaos_plan=_crash_plan(2),
                            round_timeout_s=2.0,
                            ckpt_dir=str(tmp_path / "ck"))
    assert crashed.history[-1]["round"] == 3
    _assert_bitwise(crashed.net, oracle.net)
    assert crashed.quarantine.canonical() == oracle.quarantine.canonical()
    # the counter is synced UP to the WAL's restart epoch (1 here), not
    # incremented: an earlier restarting test in this process (the registry
    # is process-wide) may already have brought it there
    assert REGISTRY.total("fed_server_restarts_total") == max(before, 1.0)
    # the WAL witnessed both boots and every commit
    from fedml_tpu.core.wal import RoundWAL

    rep = RoundWAL.replay(str(tmp_path / "ck" / "wal"))
    assert rep.restart_epochs == 2  # boot 0 + the post-crash boot
    assert rep.last_commit == 3 and rep.torn == 0


def test_mid_round_crash_ledgers_lost_slots_exactly(lr_setup, tmp_path):
    """Mid-round crash after m accepted uploads: their WAL records are
    durable, their payloads died with the process — recovery ledgers
    exactly those slots ``server_restart`` and the re-dispatched round
    folds clean (full fleet redo -> bitwise the uninterrupted run)."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    oracle = run_simulated(data, task, _cfg(), job_id="t-sc-mr-o",
                           round_timeout_s=2.0)
    crashed = run_simulated(data, task, _cfg(), job_id="t-sc-mr",
                            chaos_plan=_crash_plan(1, after_uploads=2),
                            round_timeout_s=2.0,
                            ckpt_dir=str(tmp_path / "ck"))
    assert crashed.history[-1]["round"] == 3
    _assert_bitwise(crashed.net, oracle.net)
    lost = [e for e in crashed.quarantine.entries()
            if e["reason"] == "server_restart"]
    assert len(lost) == 2 and all(e["round"] == 1 for e in lost)
    # slot-exact: loopback delivery is serial per link, so the first two
    # ACCEPTED uploads are deterministic in the ledger
    assert sorted(e["rank"] for e in lost) == sorted(
        set(e["rank"] for e in lost))  # distinct ranks, one entry each


def test_mid_round_crash_zero_uploads(lr_setup, tmp_path):
    """after_uploads=0 dies MID-ROUND with the broadcast out but zero
    uploads accepted — distinct from None (between commits): recovery
    re-dispatches the open round with nothing to ledger, and the redo
    folds bitwise the uninterrupted run."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    oracle = run_simulated(data, task, _cfg(), job_id="t-sc-z-o",
                           round_timeout_s=2.0)
    crashed = run_simulated(data, task, _cfg(), job_id="t-sc-z",
                            chaos_plan=_crash_plan(1, after_uploads=0),
                            round_timeout_s=2.0,
                            ckpt_dir=str(tmp_path / "ck"))
    assert crashed.history[-1]["round"] == 3
    _assert_bitwise(crashed.net, oracle.net)
    # zero accepted uploads died with the process -> nothing to ledger
    assert crashed.quarantine.canonical() == oracle.quarantine.canonical()
    # the crash really fired: the WAL witnessed a second boot
    from fedml_tpu.core.wal import RoundWAL

    assert RoundWAL.replay(str(tmp_path / "ck" / "wal")).restart_epochs == 2


def test_mid_round_crash_with_dead_client_is_exact_elastic_partial(
        lr_setup, tmp_path):
    """Server dies mid-round while a CLIENT is also dark: the recovered
    round folds the exact elastic partial over the ranks that answer the
    re-dispatch — bitwise the client-crash-only oracle — with the lost
    uploads ledgered on top (sample-weight-exact like PR 13's
    edge_lost)."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    client_crash = {"fault": "crash", "ranks": [3], "rounds": [1, 2]}
    oracle = run_simulated(
        data, task, _cfg(), job_id="t-sc-el-o",
        chaos_plan=FaultPlan.from_json(
            {"seed": 1, "rules": [dict(client_crash)]}),
        round_timeout_s=1.0)
    crashed = run_simulated(
        data, task, _cfg(), job_id="t-sc-el",
        chaos_plan=_crash_plan(1, after_uploads=1,
                               extra_rules=(client_crash,)),
        round_timeout_s=1.0, ckpt_dir=str(tmp_path / "ck"))
    assert crashed.history[-1]["round"] == 3
    _assert_bitwise(crashed.net, oracle.net)
    assert any(e["reason"] == "server_restart"
               for e in crashed.quarantine.entries())


def test_double_crash_same_campaign(lr_setup, tmp_path):
    """Two scheduled server crashes in one run: each consumed by one
    restart, epoch reaches 2, and the final bits still match."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    oracle = run_simulated(data, task, _cfg(rounds=5), job_id="t-sc2-o",
                           round_timeout_s=2.0)
    plan = FaultPlan.from_json({"seed": 1, "rules": [
        {"fault": "crash", "ranks": [0], "rounds": [1, 2]},
        {"fault": "crash", "ranks": [0], "rounds": [3, 4],
         "after_uploads": 1}]})
    crashed = run_simulated(data, task, _cfg(rounds=5), job_id="t-sc2",
                            chaos_plan=plan, round_timeout_s=2.0,
                            ckpt_dir=str(tmp_path / "ck"))
    assert crashed.history[-1]["round"] == 4
    _assert_bitwise(crashed.net, oracle.net)
    from fedml_tpu.core.wal import RoundWAL

    assert RoundWAL.replay(
        str(tmp_path / "ck" / "wal")).restart_epochs == 3


# ------------------------------------------------------------ async battery
def test_async_buffered_restart_liveness_and_shed(lr_setup, tmp_path):
    """Async-buffered mode through a mid-flight server crash: the
    journaled dispatch waves resume monotonic, lost buffer admissions
    are ledgered ``server_restart``, and the job completes every global
    update (liveness — async arrival order is thread-scheduled, so the
    bitwise claims stay with the sync battery)."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    agg = run_simulated(data, task, _cfg(rounds=6), job_id="t-sc-async",
                        chaos_plan=_crash_plan(2, after_uploads=1),
                        round_timeout_s=2.0,
                        ckpt_dir=str(tmp_path / "ck"),
                        async_buffer_k=3, staleness_bound=0)
    assert agg.history[-1]["round"] == 5
    assert any(e["reason"] == "server_restart"
               for e in agg.quarantine.entries())
    # wave counters resumed PAST the journaled maxima: dispatch records
    # never repeat a (rank, wave) pair across the restart
    from fedml_tpu.core.wal import RoundWAL

    rep = RoundWAL.replay(str(tmp_path / "ck" / "wal"))
    seen = [(r["rank"], r["wave"]) for r in rep.of_kind("dispatch")]
    assert len(seen) == len(set(seen))


# --------------------------------------------------------------- DP battery
def _dp_run(data, task, job, ckpt, plan=None, rounds=4):
    from fedml_tpu import chaos as _chaos
    from fedml_tpu.distributed.fedavg.api import (init_client,
                                                  run_supervised_simulated)
    from fedml_tpu.distributed.fedavg.server_manager import (
        FedAvgServerManager,
    )
    from fedml_tpu.distributed.fedavg_robust import FedAvgRobustAggregator
    from fedml_tpu.distributed.utils import backend_kwargs, launch_simulated

    size = 4
    kw = backend_kwargs("LOOPBACK", job, 50000, "127.0.0.1", 1883)
    if plan is not None:
        _chaos.install_plan(plan)
    try:
        def build():
            agg = FedAvgRobustAggregator(
                data, task, _cfg(rounds=rounds), worker_num=3,
                defense_type="dp", norm_bound=5.0, noise_multiplier=1.0)
            return FedAvgServerManager(agg, rank=0, size=size,
                                       backend="LOOPBACK", ckpt_dir=ckpt,
                                       round_timeout_s=2.0, **kw)

        server = build()
        clients = [init_client(data, task, _cfg(rounds=rounds), r, size,
                               "LOOPBACK", **kw) for r in range(1, size)]
        pts = plan.server_crash_points() if plan is not None else []
        if pts:
            server = run_supervised_simulated(server, clients, pts, build)
        else:
            launch_simulated(server, clients)
        return server.aggregator
    finally:
        if plan is not None:
            _chaos.install_plan(None)


def test_dp_crash_never_underreports_epsilon(lr_setup, tmp_path):
    """Killed-mid-round DP run: cumulative ε is never LOWER than the
    uninterrupted run's (the WAL pre-charge is fsync'd before any noise
    key is drawn); a between-commits kill lands bitwise on the oracle
    INCLUDING ε — the PR-15 resume-exact-ε contract extended to a killed
    process."""
    data, task = lr_setup
    oracle = _dp_run(data, task, "t-dp-oracle", str(tmp_path / "o"))
    mid = _dp_run(data, task, "t-dp-mid", str(tmp_path / "m"),
                  plan=_crash_plan(2, after_uploads=2))
    assert mid.epsilon() >= oracle.epsilon() - 1e-12
    bc = _dp_run(data, task, "t-dp-bc", str(tmp_path / "b"),
                 plan=_crash_plan(2))
    assert bc.epsilon() == pytest.approx(oracle.epsilon(), abs=1e-12)
    _assert_bitwise(bc.net, oracle.net)


def test_dp_precharge_replay_unit(lr_setup, tmp_path):
    """The pre-charge replay path in isolation: a WAL carrying an
    UNCOMMITTED round's precharge (crash fell between the charge and the
    commit) re-charges the restarted accountant — ε strictly above the
    checkpoint's own totals."""
    from fedml_tpu.core.wal import RoundWAL

    data, task = lr_setup
    ckpt = str(tmp_path / "ck")
    done = _dp_run(data, task, "t-dp-unit", ckpt, rounds=2)
    eps_committed = done.epsilon()
    # forge the crash artifact: round 2 opened, pre-charged, never
    # committed (the noise may or may not have been released — ε must
    # count it either way)
    wal = RoundWAL(os.path.join(ckpt, "wal"))
    wal.append("broadcast", sync=True, round=2)
    wal.append("precharge", sync=True, round=2, q=3 / 8, z=1.0,
               clip=5.0, m=3)
    wal.close()
    from fedml_tpu import chaos as _chaos
    from fedml_tpu.distributed.fedavg.server_manager import (
        FedAvgServerManager,
    )
    from fedml_tpu.distributed.fedavg_robust import FedAvgRobustAggregator
    from fedml_tpu.distributed.utils import backend_kwargs

    agg = FedAvgRobustAggregator(data, task, _cfg(rounds=4), worker_num=3,
                                 defense_type="dp", norm_bound=5.0,
                                 noise_multiplier=1.0)
    kw = backend_kwargs("LOOPBACK", "t-dp-unit2", 50000, "127.0.0.1", 1883)
    server = FedAvgServerManager(agg, rank=0, size=4, backend="LOOPBACK",
                                 ckpt_dir=ckpt, round_timeout_s=2.0, **kw)
    try:
        assert server._resume_round == 2  # the open round re-runs
        assert agg.epsilon() > eps_committed  # the charge survived the kill
    finally:
        server.com_manager.stop_receive_message()


# ----------------------------------------------------------- secagg battery
def test_secagg_mid_reveal_crash_sheds_and_retries_clean(lr_setup,
                                                         tmp_path):
    """Server crash DURING the reveal/recovery state machine: recovery
    lands in the shed-and-rebroadcast path (``secagg_shed`` ledgered for
    the slots the reveal was recovering, outcome metric counts a shed)
    and the retry reconverges bitwise to the client-crash-only oracle —
    never a half-recovered fold."""
    from fedml_tpu.distributed import turboaggregate as ta

    data, task = lr_setup
    client_crash = {"fault": "crash", "ranks": [3], "rounds": [1, 2]}
    oracle = ta.run_simulated(
        data, task, _cfg(rounds=3, per_round=4), job_id="t-ta-o",
        chaos_plan=FaultPlan.from_json(
            {"seed": 2, "rules": [dict(client_crash)]}),
        round_timeout_s=2.0)
    before = REGISTRY.snapshot().get("fed_secagg_rounds_total", {})
    before_shed = float(before.get("outcome=shed", 0.0))
    crashed = ta.run_simulated(
        data, task, _cfg(rounds=3, per_round=4), job_id="t-ta-c",
        chaos_plan=FaultPlan.from_json({"seed": 2, "rules": [
            dict(client_crash),
            {"fault": "crash", "ranks": [0], "rounds": [1, 2],
             "after_uploads": -1}]}),
        round_timeout_s=2.0, ckpt_dir=str(tmp_path / "ck"))
    assert crashed.history[-1]["round"] == 2
    reasons = {e["reason"] for e in crashed.quarantine.entries()}
    assert "secagg_shed" in reasons
    after = REGISTRY.snapshot().get("fed_secagg_rounds_total", {})
    assert float(after.get("outcome=shed", 0.0)) == before_shed + 1
    _assert_bitwise(crashed.net, oracle.net)


def test_secagg_mid_round_crash_clean_retry(lr_setup, tmp_path):
    """Masked uploads lost to a mid-round server crash: the restart
    resets the fold state (a fresh boot can never hold a partial masked
    accumulator) and the re-run round decodes clean — bitwise the
    uninterrupted masked run."""
    from fedml_tpu.distributed import turboaggregate as ta

    data, task = lr_setup
    oracle = ta.run_simulated(data, task, _cfg(rounds=3, per_round=4),
                              job_id="t-ta2-o", round_timeout_s=2.0)
    crashed = ta.run_simulated(
        data, task, _cfg(rounds=3, per_round=4), job_id="t-ta2-c",
        chaos_plan=_crash_plan(1, after_uploads=2),
        round_timeout_s=2.0, ckpt_dir=str(tmp_path / "ck"))
    assert crashed.history[-1]["round"] == 2
    _assert_bitwise(crashed.net, oracle.net)
    lost = [e for e in crashed.quarantine.entries()
            if e["reason"] == "server_restart"]
    assert len(lost) == 2


# ------------------------------------------------------------ observability
def test_restart_storm_health_rule_edge_triggers():
    from fedml_tpu.obs.health import HealthMonitor
    from fedml_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    mon = HealthMonitor(registry=reg,
                        rules=[{"rule": "restart_storm",
                                "severity": "critical",
                                "max_restarts": 2.0}])
    # not evaluable before any restart family exists / while clean
    assert mon.check() == []
    reg.counter("fed_server_restarts_total").inc(2)
    assert mon.check() == []  # at the threshold: not a storm yet
    reg.counter("fed_server_restarts_total").inc(1)
    fired = mon.check()
    assert [a["rule"] for a in fired] == ["restart_storm"]
    assert mon.check() == []  # edge-triggered: fires once
    snap = mon.snapshot()
    assert snap["status"] == "degraded"
    assert "restart_epoch" in snap


def test_healthz_and_registry_carry_restart_epoch(tmp_path):
    from fedml_tpu.obs.httpd import MetricsHTTPServer
    from fedml_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.gauge("fed_restart_epoch").set(2)
    srv = MetricsHTTPServer(port=0, registry=reg)
    try:
        assert srv.health_snapshot()["restart_epoch"] == 2
    finally:
        srv.close()


def test_report_renders_restarts_column_and_hides_on_old_logs():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "report", os.path.join(os.path.dirname(__file__), "..",
                               "scripts", "report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    new = [{"kind": "round", "round": 0, "metrics": {}, "spans": {},
            "server": {"restarts": 1, "restart_epoch": 1}}]
    old = [{"kind": "round", "round": 0, "metrics": {}, "spans": {}}]
    assert "restarts" in report.render_table(new)
    assert "restarts" not in report.render_table(old)


def test_recovery_seconds_histogram_observed(lr_setup, tmp_path):
    """Every recovering boot lands one fed_recovery_seconds observation
    (checkpoint restore + WAL replay wall time)."""
    from fedml_tpu.distributed.fedavg import run_simulated

    data, task = lr_setup
    fam_count = lambda: sum(  # noqa: E731  (histograms snapshot to
        # summary dicts keyed by label string)
        v.get("count", 0) for v in REGISTRY.snapshot().get(
            "fed_recovery_seconds", {}).values())
    before = fam_count()
    run_simulated(data, task, _cfg(rounds=3), job_id="t-rec-s",
                  chaos_plan=_crash_plan(1), round_timeout_s=2.0,
                  ckpt_dir=str(tmp_path / "ck"))
    assert fam_count() > before


def test_supervising_parent_initialises_no_backend(tmp_path):
    """One process per chip: ``--supervise`` starts the real server as a
    child, so the supervising parent — which imports jax through
    fedml_tpu.core.wal — must never initialise a backend (it would hold
    the chip its own child needs). Runs in a fresh interpreter (this test
    process has long since brought the CPU backend up)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(f"""
        import subprocess, sys

        class Child:
            pid = 4242
            def __init__(self, argv): self.argv = argv
            def wait(self): return 0

        spawned = []
        subprocess.Popen = lambda argv, **kw: spawned.append(argv) or Child(argv)
        from fedml_tpu.experiments import distributed_launch
        try:
            distributed_launch.main(["--rank", "0", "--world_size", "3",
                                     "--supervise", "2",
                                     "--ckpt_dir", {str(tmp_path / "ck")!r}])
        except SystemExit as e:
            assert e.code == 0, e.code
        from jax._src import xla_bridge
        assert "jax" in sys.modules, "parent no longer imports jax"
        assert not xla_bridge.backends_are_initialized(), "parent holds a backend"
        assert len(spawned) == 1 and "--supervise" not in spawned[0], spawned
        print("parent-clean")
    """)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "parent-clean" in proc.stdout
    assert (tmp_path / "ck" / "server.pid").read_text() == "4242"
