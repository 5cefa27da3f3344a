"""Silos folded one after another (``FedAvgConfig.client_fold="scan"``,
core/client_fold.py) against the vmapped round: the same losses and the same
model to the order of the sums; the setting moves the program store's key
and a hit counts again what the trace counted; what the fold cannot serve
is refused when the engine is built."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmark.populations import tokens
from benchmark.tests.cells.tiny_lm import SEQ_LEN, SMALL
from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.core import local
from fedml_tpu.core.tasks import classification_task, routed_sequence_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.models import create_model
from fedml_tpu.models.lfm2_moe import Lfm2MoeLM
from fedml_tpu.models.resnet import ResNetCIFAR
from fedml_tpu.obs import perf_instrument as perf
from fedml_tpu.obs.metrics import REGISTRY
from tests.test_program_store import (_delta, _key,  # noqa: F401
                                      cache_dir)


@pytest.fixture(scope="module")
def images():
    return synthetic_images(num_clients=4, image_shape=(8, 8, 3),
                            num_classes=4, samples_per_client=20,
                            test_samples=8, seed=1, size_lognormal=True)


@pytest.fixture(scope="module")
def sequences():
    return tokens.make({"num_clients": 3, "vocab_size": SMALL["vocab_size"],
                        "seq_len": SEQ_LEN, "sequences_per_client": 6,
                        "topics": 4, "zipf_exponent": 1.1,
                        "dirichlet_alpha": 0.5, "test_sequences": 2}, 7)


def _cfg(**kw):
    base = dict(comm_round=4, client_num_in_total=4, client_num_per_round=3,
                epochs=1, batch_size=8, lr=0.05, wd=0.001, seed=3,
                max_batches=2, frequency_of_the_test=100)
    base.update(kw)
    return FedAvgConfig(**base)


def _run(data, task, cfg, rounds=3, **kw):
    api = FedAvgAPI(data, task, cfg, device_data=True, **kw)
    ms = jax.block_until_ready(api.run_rounds(0, rounds))
    return api, {k: np.asarray(v) for k, v in ms.items()}


def _engines(name, images, sequences):
    if name == "resnet":
        task = classification_task(ResNetCIFAR(depth=8, num_classes=4,
                                               norm_type="group"))
        return images, task, _cfg()
    task = routed_sequence_task(Lfm2MoeLM(**dict(SMALL, moe_row_budget=4.0)))
    return sequences, task, _cfg(client_num_in_total=3, batch_size=2, wd=0.0)


@pytest.mark.parametrize("name", ["resnet", "lfm2_moe"])
def test_fold_equals_vmapped_round(name, images, sequences):
    """Three rounds of uneven silos: every loss and every leaf."""
    data, task, cfg = _engines(name, images, sequences)
    vm, ms_v = _run(data, task, cfg)
    sc, ms_s = _run(data, task, dataclasses.replace(cfg, client_fold="scan"))
    assert set(ms_s) == set(ms_v) == {"loss_sum", "correct", "count"}
    for k in ms_s:
        np.testing.assert_allclose(ms_s[k], ms_v[k], rtol=2e-6)
    # the vmapped expert layer runs both of its paths and selects; its
    # batched sums run in another order than one silo's
    atol = 2e-6 if name == "resnet" else 1e-5
    for a, b in zip(jax.tree.leaves(vm.net), jax.tree.leaves(sc.net)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=atol * (1 + np.abs(b).max()))


def test_fold_runs_the_result_hook_on_each_silo(images):
    """A clipping hook, with the keys the vmapped round hands it."""
    def shrink(net_k, net, key):
        scale = 0.5 + 0.1 * jax.random.uniform(key)
        return jax.tree.map(lambda new, old: old + scale * (new - old),
                            net_k, net)

    task = classification_task(create_model("lr", output_dim=4))
    vm, _ = _run(images, task, _cfg(), client_result_hook=shrink)
    sc, _ = _run(images, task, _cfg(client_fold="scan"),
                 client_result_hook=shrink)
    for a, b in zip(jax.tree.leaves(vm.net), jax.tree.leaves(sc.net)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


@pytest.mark.parametrize("fold", ["scan", "vmap"])
def test_routing_counts_reach_the_registry_unread(sequences, fold):
    """The block's metrics keep their three sums whichever way the cohort
    runs: the task declares the model's counts handed off, and they are
    summed on the device until the registry is asked."""
    task = routed_sequence_task(Lfm2MoeLM(**SMALL))
    assert task.handoff == ("moe_stats_", perf.note_moe_stats)
    before = perf.moe_rows()  # reads whatever other tests left
    tokens0 = REGISTRY.total("fed_moe_expert_tokens_total")
    api, ms = _run(sequences, task,
                   _cfg(client_num_in_total=3, batch_size=2, wd=0.0,
                        client_fold=fold), rounds=2)
    assert set(ms) == {"loss_sum", "correct", "count"}
    assert set(perf._moe_sum) == {"rows_real", "rows_dispatched",
                                  "fallback_steps", "expert_tokens"}
    rows = perf.moe_rows()
    assert perf._moe_sum is None
    real = rows["real"] - before["real"]
    assert 0 < real <= rows["dispatched"] - before["dispatched"]
    assert REGISTRY.total("fed_moe_fallback_steps_total") >= 0
    # 2 rounds x 3 silos x 2 batches of 2 x 32 tokens, 2 experts a token:
    # the held share of them, whatever the routing
    assert real <= 2 * 3 * 2 * 2 * SEQ_LEN * SMALL["num_experts_per_tok"] * 4
    # by expert where the round keeps the counts' shape: the vmapped round
    # sums a metric over all its axes
    by_expert = REGISTRY.total("fed_moe_expert_tokens_total") - tokens0
    assert by_expert == (real if fold == "scan" else 0)
    if fold == "scan":
        fam = REGISTRY.snapshot()["fed_moe_expert_tokens_total"]
        assert {"expert=0,layer=0", "expert=1,layer=3"} <= set(fam)


def test_a_task_without_a_hand_off_gets_the_program_itself(images):
    task = classification_task(create_model("lr", output_dim=4))
    assert task.handoff is None
    api = FedAvgAPI(images, task, _cfg(), device_data=True)
    assert not isinstance(api._build_block_fn(), local._HandingOff)
    routed = task._replace(handoff=("moe_stats_", perf.note_moe_stats))
    api = FedAvgAPI(images, routed, _cfg(), device_data=True)
    assert isinstance(api._build_block_fn(), local._HandingOff)


def test_many_blocks_keep_one_sum_on_the_device():
    """Past 2**32 too: the sum carries into a second limb."""
    REGISTRY.snapshot()
    before = perf.moe_rows()
    stats = {"rows_real": np.full(2, 1.5e9, np.float32),
             "rows_dispatched": np.full(2, 2.0e9, np.float32)}
    for _ in range(5):
        perf.note_moe_stats(stats)
    high, low = perf._moe_sum["rows_real"]
    assert isinstance(low, jax.Array) and int(high) == 3
    rows = perf.moe_rows()
    assert rows["real"] - before["real"] == 15e9
    assert rows["dispatched"] - before["dispatched"] == 20e9
    # another model's counts: what was summed is read, then a new sum
    perf.note_moe_stats(stats)
    perf.note_moe_stats({"expert_tokens": np.ones((2, 3, 4), np.float32)})
    assert set(perf._moe_sum) == {"expert_tokens"}
    assert perf.moe_rows()["real"] - before["real"] == 18e9


def test_a_vmapped_cohort_beyond_the_device_is_refused(images, monkeypatch):
    from fedml_tpu.algorithms import fedavg

    task = classification_task(create_model("lr", output_dim=4))
    FedAvgAPI(images, task, _cfg(), device_data=True)  # the CPU says nothing
    monkeypatch.setattr(fedavg, "_device_bytes_limit", lambda: 4096)
    with pytest.raises(ValueError, match="client_fold='scan'"):
        FedAvgAPI(images, task, _cfg(), device_data=True)
    FedAvgAPI(images, task, _cfg(client_fold="scan"), device_data=True)


def test_client_fold_moves_the_key_and_a_hit_replays_the_count(
        images, cache_dir):
    task = classification_task(create_model("lr", output_dim=4))
    vm = FedAvgAPI(images, task, _cfg(), device_data=True)
    sc = FedAvgAPI(images, task, _cfg(client_fold="scan"), device_data=True)
    assert sc._block_trace_reads()["client_fold"] == "scan"
    assert _key(sc) != _key(vm)

    c0, folds0 = perf.program_store_counts(), perf.client_fold_counts()
    jax.block_until_ready(sc.run_rounds(0, 2))
    assert _delta(c0) == {"miss": 1.0}
    traced = perf.client_fold_counts()["scan"] - folds0.get("scan", 0)
    assert traced >= 1
    again = FedAvgAPI(images, task, _cfg(client_fold="scan"),
                      device_data=True)
    jax.block_until_ready(again.run_rounds(0, 2))
    assert _delta(c0) == {"miss": 1.0, "hit": 1.0}
    # the hit traced nothing and counts what the miss's trace counted
    assert perf.client_fold_counts()["scan"] - folds0.get("scan", 0) \
        == 2 * traced
    for a, b in zip(jax.tree.leaves(sc.net), jax.tree.leaves(again.net)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kept_outputs_are_counted_at_trace_and_a_hit_replays_them(
        sequences, cache_dir):
    """``fed_remat_sites_total`` and ``fed_remat_kept_bytes_total``
    (models/lfm2_moe.py ``kept_names``): the block's trace counts each
    named output of each block and the bytes the rule keeps, the store
    keeps them in the record's header, and a hit counts them again."""
    from fedml_tpu.models import lfm2_moe

    sizes = dict(SMALL, moe_row_budget=4.0)
    model = Lfm2MoeLM(**sizes)
    task = routed_sequence_task(model)
    cfg = _cfg(client_num_in_total=3, batch_size=2, wd=0.0,
               client_fold="scan")
    plan, step_bytes = lfm2_moe.kept_names(
        model.sizes(), model.layer_types, model.num_dense_layers, 2, SEQ_LEN,
        4, lfm2_moe.KEPT_BYTES)
    assert step_bytes > 0 and all(all(layer.values()) for layer in plan)

    def counted(run):
        c0 = perf.remat_counts()
        run()
        c1 = perf.remat_counts()
        return ({k: n - c0["sites"].get(k, 0)
                 for k, n in c1["sites"].items()
                 if n > c0["sites"].get(k, 0)},
                c1["kept_bytes"] - c0["kept_bytes"])

    api = FedAvgAPI(sequences, task, cfg, device_data=True)
    s0 = perf.program_store_counts()
    sites, nbytes = counted(
        lambda: jax.block_until_ready(api.run_rounds(0, 2)))
    assert _delta(s0) == {"miss": 1.0}
    # every name is kept at this size; a name counts once a block that has
    # it, in each trace of the model, and the bytes are the rule's own
    traces = sites["attn_q", "yes"] / SMALL["layer_types"].count(
        "full_attention")
    assert traces >= 1 and nbytes == traces * step_bytes
    assert sites == {(name, "yes"): traces * sum(name in layer
                                                 for layer in plan)
                     for layer in plan for name in layer}
    again = FedAvgAPI(sequences, task, cfg, device_data=True)
    replayed = counted(
        lambda: jax.block_until_ready(again.run_rounds(0, 2)))
    assert _delta(s0) == {"miss": 1.0, "hit": 1.0}
    assert replayed == (sites, nbytes)
    # a record from before the counters has no such key
    before = perf.remat_counts()
    perf.replay_traced({"conv_sites": [], "client_fold": [["scan", 1]]})
    assert perf.remat_counts() == before


class _Overrides(FedAvgAPI):
    def _round_body(self, *a, **kw):
        return super()._round_body(*a, **kw)


@pytest.mark.parametrize("what,build", [
    ("device_data", lambda d, t, c: FedAvgAPI(d, t, c)),
    ("robust", lambda d, t, c: FedAvgAPI(d, t, c, device_data=True,
                                         aggregator="median")),
    ("sanitiz", lambda d, t, c: FedAvgAPI(d, t, c, device_data=True,
                                          sanitize=True)),
    ("overrides", lambda d, t, c: _Overrides(d, t, c, device_data=True)),
    ("one of", lambda d, t, c: FedAvgAPI(
        d, t, dataclasses.replace(c, client_fold="pmap"), device_data=True)),
])
def test_what_the_fold_cannot_serve_is_refused(images, what, build):
    task = classification_task(create_model("lr", output_dim=4))
    with pytest.raises(ValueError, match=what):
        build(images, task, _cfg(client_fold="scan"))


def test_fold_refuses_a_mesh_and_the_per_round_driver(images):
    from jax.sharding import Mesh

    task = classification_task(create_model("lr", output_dim=4))
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("clients",))
    with pytest.raises(ValueError, match="mesh"):
        FedAvgAPI(images, task, _cfg(client_fold="scan",
                                     client_num_per_round=2),
                  mesh=mesh, device_data=True)
    api = FedAvgAPI(images, task, _cfg(client_fold="scan"), device_data=True)
    with pytest.raises(ValueError, match="run_rounds"):
        api.run_round(0)
    with pytest.raises(ValueError, match="per_round=False"):
        api.warmup(block_rounds=2)
    assert "block_r2" in " ".join(
        api.warmup(block_rounds=2, per_round=False)["variants"])
