"""The round engine's one span path (docs/OBSERVABILITY.md §Engine spans):
``RoundTracer`` spans as totals AND as ``fed:`` events of the jax profiler's
trace, the three named scopes of the round program in every driver's lowered
text, and set-up accounted per round program by the compile observatory.
"""

import glob

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_lr
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import perf_instrument as perf

SCOPES = ("fed_gather", "fed_aggregate", "fed_server_update")


@pytest.fixture(scope="module")
def lr_data():
    # dim 20: divisible by the 4-device mesh, so the kernel really shards
    return synthetic_lr(num_clients=8, dim=20, num_classes=5, seed=0)


@pytest.fixture(scope="module")
def lr_task():
    return classification_task(LogisticRegression(num_classes=5))


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.asarray(jax.devices()[:4]), ("clients",))


def _api(data, task, **kw):
    cfg = FedAvgConfig(comm_round=6, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=16,
                       lr=0.05, seed=0, max_batches=4,
                       frequency_of_the_test=100)
    return FedAvgAPI(data, task, cfg, device_data=True, **kw)


def _half_step(old, avg, opt_state):
    """A server update that computes something: plain FedAvg's is the
    identity and puts no op under ``fed_server_update``."""
    return jax.tree.map(lambda o, a: o + 0.5 * (a - o), old, avg), opt_state


def _leaves(api):
    return [np.asarray(v) for v in jax.tree.leaves(api.net.params)]


@pytest.mark.parametrize("drive", ["run_rounds", "run_round"])
def test_drivers_fill_the_engine_spans(lr_data, lr_task, drive):
    api = _api(lr_data, lr_task)
    assert api.tracer.totals()["init"] > 0.0      # closed by __init__
    out = api.run_rounds(0, 2) if drive == "run_rounds" else api.run_round(0)
    # no profiler, no telemetry: device arrays back, nothing fetched
    assert all(isinstance(v, jax.Array) for v in out.values())
    tot = api.tracer.totals()
    assert {"init", "pack", "round"} <= set(tot)
    assert "fetch" not in tot
    assert tot["pack"] > 0.0 and tot["round"] > 0.0
    # the per-round path places its batch in a span of its own; the
    # scanned block keeps its two spans as they were (PERF.md section 6)
    if drive == "run_round":
        assert 0.0 < tot["place"] <= tot["pack"]
    else:
        assert "place" not in tot


def _fed_events(tracedir):
    """{name: [(start_ns, end_ns, stats)]} of the python thread's ``fed:``
    events in the one trace under ``tracedir``."""
    path, = glob.glob(f"{tracedir}/plugins/profile/*/*.xplane.pb")
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fed:"):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return events


def test_per_round_spans_are_events_of_the_profilers_trace(lr_data, lr_task,
                                                           tmp_path):
    api = _api(lr_data, lr_task)
    api.run_round(0)                      # compile outside the traced part
    ids = [2, 3]
    with jax.profiler.trace(str(tmp_path)):
        for r in ids:
            jax.block_until_ready(api.run_round(r))
    ev = _fed_events(tmp_path)
    assert {"fed:pack", "fed:place", "fed:round"} <= set(ev)
    for name in ("fed:pack", "fed:place", "fed:round"):
        # the keyword lands as a stat of the event: the unit's round
        assert [e[2].get("round") for e in sorted(ev[name])] == ids, name
    for (ps, pe, _), (cs, ce, _), (rs, _, _) in zip(
            sorted(ev["fed:pack"]), sorted(ev["fed:place"]),
            sorted(ev["fed:round"])):
        assert ps <= cs and ce <= pe      # place nests inside pack
        assert rs >= pe                   # the dispatch follows the pack


def test_block_spans_are_events_of_the_profilers_trace(lr_data, lr_task,
                                                       tmp_path):
    api = _api(lr_data, lr_task)
    api.run_rounds(0, 2)
    with jax.profiler.trace(str(tmp_path)):
        for r in (2, 4):                  # two units of two rounds
            jax.block_until_ready(api.run_rounds(r, 2))
    ev = _fed_events(tmp_path)
    assert len(ev["fed:pack"]) == 2 and len(ev["fed:round"]) == 2
    for (_, pe, _), (rs, _, _) in zip(sorted(ev["fed:pack"]),
                                      sorted(ev["fed:round"])):
        assert rs >= pe                   # the dispatch follows the pack


def test_prefetch_thread_annotates_without_the_tracer(lr_data, lr_task,
                                                      tmp_path):
    api = _api(lr_data, lr_task, prefetch=2)
    api.run_pipelined(0, 1)
    with jax.profiler.trace(str(tmp_path)):
        api.run_pipelined(1, 3)
    ev = _fed_events(tmp_path)
    assert len(ev["fed:prefetch_pack"]) == 3 and len(ev["fed:h2d"]) == 3
    assert sorted(e[2]["round"] for e in ev["fed:h2d"]) == [1, 2, 3]
    # the packer thread's intervals never reach the tracer's dict
    assert not {"prefetch_pack", "h2d", "place"} & set(api.tracer.totals())


def _block_args(api, rounds=2):
    ids_l, (dev_x, dev_y, blocks, rnds) = api._place_block(
        api._pack_block_host(0, rounds))
    return (api.rng, api.net, api.server_opt_state, dev_x, dev_y, *blocks,
            rnds)


@pytest.mark.parametrize("program", ["block", "mesh_block", "sharded_block",
                                     "per_round"])
def test_lowered_round_programs_name_the_three_scopes(lr_data, lr_task,
                                                      mesh4, program):
    kw = {"mesh_block": dict(mesh=mesh4),
          "sharded_block": dict(mesh=mesh4, shard_server_state=True)}
    api = _api(lr_data, lr_task, server_update=_half_step,
               **kw.get(program, {}))
    if program == "per_round":
        ids = api._sampled_ids(0)
        lowered = api.round_fn.lower(
            api.rng, api.net, api.server_opt_state, api._pack_round(0),
            jax.numpy.int32(0), jax.numpy.asarray(ids, jax.numpy.int32))
    else:
        api._block_fn = api._build_block_fn()
        lowered = api._block_fn.lower(*_block_args(api))
    text = lowered.as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, f"{program}: no op under {scope}"


def test_robust_aggregate_sits_under_the_aggregate_scope(lr_data, lr_task):
    api = _api(lr_data, lr_task, aggregator="median")
    api._block_fn = api._build_block_fn()
    text = api._block_fn.lower(*_block_args(api)).as_text(debug_info=True)
    assert "fed_aggregate/" in text
    # plain FedAvg's server update is the identity: the scope holds no op
    assert "fed_server_update" not in text


def test_first_dispatch_is_accounted_to_its_variant(lr_data, lr_task):
    """A fresh engine's first dispatch traces, lowers and compiles (or
    loads) under its own name: the per-round program under its variant's
    (a scope around the call), the scanned block under the jit function's
    (``block_fn``, from the name jax hands the listener: its two frames
    stay untouched). Nothing of either lands in ``_other``."""
    # shapes no other test of this process dispatches, so the jit cache of
    # the process cannot have them (the persistent cache may: a hit still
    # traces and lowers)
    cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                       client_num_per_round=5, epochs=1, batch_size=12,
                       lr=0.05, seed=3, max_batches=3,
                       frequency_of_the_test=100)
    api = FedAvgAPI(lr_data, lr_task, cfg, device_data=True)
    stats0 = perf.variant_compile_stats()
    other0 = dict(stats0.get(perf.UNATTRIBUTED_VARIANT, {}))
    init = stats0[perf.INIT_VARIANT]
    assert init["compiles"] >= 1.0 and init["trace_seconds"] > 0.0
    phases0 = perf.setup_phases()

    jax.block_until_ready(api.run_rounds(0, 2))
    jax.block_until_ready(api.run_round(2))
    stats = perf.variant_compile_stats()
    block0 = stats0.get("block_fn", {})
    for variant, before in (("block_fn", block0),
                            (api._variant_name(), {})):
        st = stats[variant]
        for key in ("trace_seconds", "lower_seconds", "compiles", "seconds"):
            assert st[key] > before.get(key, 0.0), (variant, key)
    # `_other` gains no program: only what carries no round program's
    # name, which is the cache's hit, miss and retrieval events of the
    # untagged block dispatch and the traces of the jits nested in it
    # (jnp's own), which the block's trace time already holds
    other = stats.get(perf.UNATTRIBUTED_VARIANT, {})
    for key in ("lower_seconds", "compiles", "seconds"):
        assert other.get(key, 0.0) == other0.get(key, 0.0), key

    phases = perf.setup_phases()
    assert set(phases) == {"init_s", "trace_s", "lower_s",
                           "compile_or_load_s"}
    assert phases["init_s"] >= api.tracer.totals()["init"]
    for key in ("trace_s", "lower_s", "compile_or_load_s"):
        assert phases[key] > phases0[key]
    # the variant's share of set-up sits inside the first calls' `round`
    # spans (trace + lower + compile-or-load + an enqueue), up to what
    # nested jits report twice
    spent = sum(phases[k] - phases0[k]
                for k in ("trace_s", "lower_s", "compile_or_load_s"))
    assert spent <= 1.5 * api.tracer.totals()["round"]

    # a second dispatch of each compiles nothing and reports nothing
    jax.block_until_ready(api.run_rounds(3, 2))
    jax.block_until_ready(api.run_round(5))
    assert perf.variant_compile_stats() == stats


def test_duration_events_land_under_the_attributed_variant():
    assert perf._compile_variant("jit(block_fn)") == "block_fn"
    assert perf._compile_variant("round_fn") == "round_fn"
    assert perf._compile_variant("jit(_take)") == perf.UNATTRIBUTED_VARIANT
    with perf.attribute_compiles("unit_probe_v1"):
        assert perf._compile_variant("jit(block_fn)") == "unit_probe_v1"
        perf._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25,
                          fun_name="anything")
        perf._on_duration(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5)
        perf._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        perf._on_duration("/jax/core/compile/backend_compile_duration", 1.0)
    st = perf.variant_compile_stats()["unit_probe_v1"]
    assert (st["trace_seconds"], st["lower_seconds"],
            st["cache_retrieval_seconds"], st["seconds"]) \
        == (0.25, 0.5, 0.125, 1.0)


def test_per_round_with_explicit_placement_equals_block(lr_data, lr_task):
    """``run_round`` now places its batch in the ``place`` span instead of
    letting the dispatch transfer it: same bits as the scanned block."""
    a = _api(lr_data, lr_task)
    b = _api(lr_data, lr_task)
    ms = a.run_rounds(0, 4)
    per = [b.run_round(r) for r in range(4)]
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)
    for k in ms:
        np.testing.assert_array_equal(
            np.asarray(ms[k]), np.stack([np.asarray(m[k]) for m in per]))
    # and the host-packed plane, whose whole batch is what gets placed
    c = FedAvgAPI(lr_data, lr_task, a.cfg)
    for r in range(4):
        c.run_round(r)
    for x, y in zip(_leaves(a), _leaves(c)):
        np.testing.assert_array_equal(x, y)
