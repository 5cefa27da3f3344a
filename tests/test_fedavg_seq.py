"""Long-context federated engine: FedAvg over a ('clients','seq') mesh.

The per-client local fit runs ring attention over the 'seq' axis with
grad-psum; the oracle is the plain single-device engine on the identical
config — ring attention ≡ full attention and psum-ed grads ≡ unsharded
grads, so the trained parameters must match to float-summation order.
"""

import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
from fedml_tpu.algorithms.fedavg_seq import FedAvgSeqAPI
from fedml_tpu.core.tasks import sequence_task
from fedml_tpu.data.synthetic import synthetic_sequences
from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.utils.tree import tree_global_norm, tree_sub

def _rel(a, b):
    """Relative parameter distance ||a - b|| / ||a|| between two nets."""
    return float(tree_global_norm(tree_sub(a.params, b.params))
                 ) / float(tree_global_norm(a.params))


def _mesh(cd, sd):
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()[: cd * sd]
    return Mesh(np.asarray(devs).reshape(cd, sd), ("clients", "seq"))


def _model_ctor(seq_axis):
    return TransformerLM(vocab_size=32, dim=16, depth=1, num_heads=2,
                         max_len=16, seq_axis=seq_axis)


@pytest.fixture(scope="module")
def seq_data():
    return synthetic_sequences(num_clients=8, seq_len=16, vocab_size=32,
                               samples_per_client=12, test_samples=40, seed=2)


def test_seq_parallel_fedavg_equals_single_device(seq_data):
    cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0)

    oracle = FedAvgAPI(seq_data, sequence_task(_model_ctor(None)), cfg)
    sp = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2))
    for r in range(3):
        m_o = oracle.run_round(r)
        m_s = sp.run_round(r)
    rel = _rel(oracle.net, sp.net)
    assert rel < 1e-5, rel
    # metrics agree too (counts exactly, sums to float tolerance)
    np.testing.assert_allclose(float(m_o["count"]), float(m_s["count"]))
    np.testing.assert_allclose(float(m_o["loss_sum"]), float(m_s["loss_sum"]),
                               rtol=1e-4)


def test_seq_size_weighted_equals_single_device(seq_data):
    """--sampling size_weighted on the long-context engine: same sampler +
    forced-uniform aggregate as FedAvgAPI, so mesh ≡ single device holds
    for the weighted scheme too. Client sizes are SKEWED so the uniform
    aggregate is numerically observable — if the seq engine regressed to
    the sample-weighted mean, the oracle comparison would diverge."""
    from fedml_tpu.core.client_data import FederatedData

    rs = np.random.RandomState(0)
    perm = rs.permutation(len(seq_data.train_x))
    cuts = np.cumsum([30, 20, 14, 10, 8, 6, 5])  # sizes 30..3 over 96 rows
    idx_map = {c: np.sort(part) for c, part in
               enumerate(np.split(perm, cuts))}
    skewed = FederatedData(seq_data.train_x, seq_data.train_y,
                           seq_data.test_x, seq_data.test_y,
                           idx_map, seq_data.test_idx_map,
                           seq_data.class_num)

    cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0,
                       sampling="size_weighted")
    oracle = FedAvgAPI(skewed, sequence_task(_model_ctor(None)), cfg)
    sp = FedAvgSeqAPI(skewed, _model_ctor, cfg, mesh=_mesh(2, 2))
    assert oracle.uniform_avg and sp.uniform_avg
    for r in range(2):
        np.testing.assert_array_equal(  # same draws from the shared sampler
            oracle._sampled_ids(r), sp._sampled_ids(r))
        oracle.run_round(r)
        sp.run_round(r)
    rel = _rel(oracle.net, sp.net)
    assert rel < 1e-5, rel


def test_seq_parallel_learns_and_evaluates(seq_data):
    cfg = FedAvgConfig(comm_round=6, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.2, frequency_of_the_test=2, seed=1)
    sp = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(4, 2))
    sp.train()
    losses = [h["train_loss"] for h in sp.history]
    assert losses[-1] < losses[0]
    assert sp.history[-1]["test_acc"] > 0.0


def test_seq_mesh_validation(seq_data):
    cfg = FedAvgConfig(comm_round=1, client_num_in_total=8,
                       client_num_per_round=4, batch_size=6, lr=0.1)
    with pytest.raises(ValueError, match="divisible"):
        FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(1, 3))


def test_seq_parallel_ulysses_equals_single_device(seq_data):
    """Ulysses (all-to-all head scatter) as the seq impl: same mesh ==
    single-device equivalence as the ring path (heads % seq shards == 0)."""
    def ctor(seq_axis):
        return TransformerLM(vocab_size=32, dim=16, depth=1, num_heads=2,
                             max_len=16, seq_axis=seq_axis, seq_impl="ulysses")

    cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0)
    oracle = FedAvgAPI(seq_data, sequence_task(ctor(None)), cfg)
    sp = FedAvgSeqAPI(seq_data, ctor, cfg, mesh=_mesh(2, 2))
    for r in range(2):
        oracle.run_round(r)
        sp.run_round(r)
    rel = _rel(oracle.net, sp.net)
    assert rel < 1e-5, rel


def test_seq_parallel_fedopt_server(seq_data):
    """FedOpt-style server optimizer on the long-context engine: server
    SGD(lr=1, momentum=0) on the pseudo-gradient == plain FedAvg."""
    from fedml_tpu.algorithms.fedopt import (make_fedopt_server_update,
                                             make_server_optimizer)

    tx = make_server_optimizer("sgd", 1.0, 0.0)
    server_update = make_fedopt_server_update(tx)

    cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0)
    plain = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2))
    opt = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2),
                       server_update=server_update, server_opt_init=tx.init)
    for r in range(2):
        plain.run_round(r)
        opt.run_round(r)
    rel = _rel(plain.net, opt.net)
    assert rel < 1e-6, rel


def test_seq_run_rounds_block_equals_sequential(seq_data):
    """The R-round scan block on the two-axis mesh == R sequential
    run_round calls (same fold_in chain, same packing, same psums)."""
    cfg = FedAvgConfig(comm_round=3, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0)
    seq = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2))
    for r in range(3):
        seq.run_round(r)
    blk = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2))
    ms = blk.run_rounds(0, 3)
    assert ms["count"].shape == (3,)
    rel = _rel(seq.net, blk.net)
    assert rel < 1e-6, rel


def test_seq_parallel_fedprox_equals_single_device(seq_data):
    """FedProx on long context: the proximal term is over seq-INVARIANT
    params (computed identically on every shard, no collective), so the
    sharded engine must match the single-device FedProxAPI exactly."""
    from fedml_tpu.algorithms.fedprox import FedProxAPI

    cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0)
    from fedml_tpu.algorithms.fedavg import make_client_optimizer
    from fedml_tpu.core.local import LocalSpec

    oracle = FedProxAPI(seq_data, sequence_task(_model_ctor(None)), cfg, mu=0.3)
    spec = LocalSpec(optimizer=make_client_optimizer(cfg), epochs=cfg.epochs,
                     prox_mu=0.3)
    sp = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2),
                      local_spec=spec)
    for r in range(2):
        oracle.run_round(r)
        sp.run_round(r)
    rel = _rel(oracle.net, sp.net)
    assert rel < 1e-5, rel
    # mu actually bites: differs from plain FedAvg on the same config
    plain = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2))
    for r in range(2):
        plain.run_round(r)
    diff = float(tree_global_norm(tree_sub(plain.net.params, sp.net.params)))
    assert diff > 1e-4, diff


def test_seq_load_state_roundtrips_checkpoint(seq_data, tmp_path):
    """The CLI resume path (experiments/cli.py) calls api.load_state for
    every engine it checkpoints — including this one. Restored state must
    land replicated over the 2-axis mesh and keep training."""
    import jax

    from fedml_tpu.core.checkpoint import latest_round, restore_round, save_round

    cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                       client_num_per_round=2, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0)
    api = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2))
    api.run_round(0)
    save_round(str(tmp_path), 0, api.net, api.server_opt_state, api.rng)

    api2 = FedAvgSeqAPI(seq_data, _model_ctor, cfg, mesh=_mesh(2, 2))
    tmpl = {"net": api2.net, "server_opt_state": api2.server_opt_state,
            "rng": api2.rng, "round": 0}
    st = restore_round(str(tmp_path), latest_round(str(tmp_path)), tmpl)
    api2.load_state(st["net"], st["server_opt_state"], st["rng"])
    rel = _rel(api.net, api2.net)
    assert rel < 1e-7, rel
    api2.run_round(1)  # restored state actually trains on the mesh
    assert all(bool(np.isfinite(v).all())
               for v in jax.tree.leaves(jax.device_get(api2.net.params)))


def test_seq_parallel_flash_equals_single_device(seq_data):
    """use_flash inside the FL engine under the strict (check_vma=True)
    grad transpose: flash ring attention ≡ dense ring ≡ single-device
    oracle (the round-1 rejection of use_flash is lifted)."""
    cfg = FedAvgConfig(comm_round=2, client_num_in_total=8,
                       client_num_per_round=4, epochs=1, batch_size=6,
                       lr=0.1, frequency_of_the_test=100, seed=0)

    def flash_ctor(seq_axis):
        return TransformerLM(vocab_size=32, dim=16, depth=1, num_heads=2,
                             max_len=16, seq_axis=seq_axis, use_flash=True)

    oracle = FedAvgAPI(seq_data, sequence_task(_model_ctor(None)), cfg)
    sp = FedAvgSeqAPI(seq_data, flash_ctor, cfg, mesh=_mesh(2, 2))
    for r in range(2):
        oracle.run_round(r)
        sp.run_round(r)
    rel = _rel(oracle.net, sp.net)
    assert rel < 1e-4, rel
