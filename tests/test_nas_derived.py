"""The DARTS search space and what is derived from it: supernet forward,
genotype extraction and files, derived CIFAR / ImageNet networks, the
search -> derive -> train path, the auxiliary head."""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.models.darts import DARTSNetwork, extract_genotype, num_edges, PRIMITIVES


def test_darts_supernet_forward():
    """Full search space: 8 primitives, separate normal/reduce alphas, and
    reduction cells (layers=3 -> reduce at 1, 2) halving spatial dims."""
    assert len(PRIMITIVES) == 8  # genotypes.py:5-14 parity
    assert {"sep_conv_5x5", "dil_conv_5x5"} <= set(PRIMITIVES)
    x = jnp.zeros((2, 16, 16, 3))
    net = DARTSNetwork(num_classes=5, layers=3, init_filters=8)
    v = net.init(jax.random.PRNGKey(0), x, train=False)
    out = net.apply(v, x, train=False)
    assert out.shape == (2, 5)
    assert v["params"]["alphas_normal"].shape == (num_edges(4), len(PRIMITIVES))
    assert v["params"]["alphas_reduce"].shape == (num_edges(4), len(PRIMITIVES))


def test_genotype_extraction():
    x = jnp.zeros((1, 8, 8, 3))
    net = DARTSNetwork(num_classes=3, layers=1, init_filters=8)
    v = net.init(jax.random.PRNGKey(0), x, train=False)
    geno = extract_genotype(v["params"])
    # reference Genotype structure: normal/normal_concat/reduce/reduce_concat
    assert geno["normal_concat"] == [2, 3, 4, 5]
    assert geno["reduce_concat"] == [2, 3, 4, 5]
    for cell in ("normal", "reduce"):
        gene = geno[cell]
        assert len(gene) == 8  # 2 edges per node x 4 nodes, flat like the reference
        for op, pred in gene:
            assert op in PRIMITIVES and op != "none"
        # node i can only read from states 0..i+1
        for i in range(4):
            for op, pred in gene[2 * i : 2 * i + 2]:
                assert 0 <= pred < 2 + i


def test_as_genotype_json_file_normalizes_like_dict(tmp_path):
    """ADVICE r5 item 4: the json-FILE branch must apply the same (op, int)
    normalization/validation as dict input — a file with float node indices
    (json has no int/float distinction for some producers) must come back
    int-indexed, and garbage must fail fast, not deep inside DerivedCell."""
    import json

    import pytest

    from fedml_tpu.models.darts import GENOTYPES, as_genotype

    g = {k: (list(v) if isinstance(v, tuple) else v)
         for k, v in GENOTYPES["FedNAS_V1"].items()}
    g["normal"] = [[op, float(j)] for op, j in g["normal"]]  # float indices
    g["normal_concat"] = [float(i) for i in g["normal_concat"]]
    p = tmp_path / "geno.json"
    p.write_text(json.dumps(g))
    out = as_genotype(str(p))
    assert out["normal"] == as_genotype(GENOTYPES["FedNAS_V1"])["normal"]
    assert all(isinstance(i, int) for i in out["normal_concat"])

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"normal": [["sep_conv_3x3", "x"]],
                               "normal_concat": [2],
                               "reduce": [], "reduce_concat": []}))
    with pytest.raises((ValueError, TypeError)):
        as_genotype(str(bad))


def test_derived_network_forward_and_drop_path():
    """NetworkCIFAR (model.py:111): eval returns logits; train returns
    (logits, logits_aux) with aux=None when the head is off; drop-path is
    train-only stochasticity (utils.py drop_path)."""
    from fedml_tpu.models.darts import NetworkCIFAR

    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3))
    net = NetworkCIFAR(genotype="DARTS_V2", num_classes=5, layers=3,
                       init_filters=8, auxiliary=False, drop_path_prob=0.5)
    v = net.init(jax.random.PRNGKey(0), x, train=False)
    out = net.apply(v, x, train=False)
    assert out.shape == (4, 5)
    # without the aux head the net returns BARE logits even in train mode
    # (usable by classification_task / create_model)
    tr1 = net.apply(v, x, train=True,
                    rngs={"dropout": jax.random.PRNGKey(2)})
    assert tr1.shape == (4, 5)
    tr2 = net.apply(v, x, train=True,
                    rngs={"dropout": jax.random.PRNGKey(3)})
    assert not np.allclose(tr1, tr2)  # drop-path active during training
    # eval path has no stochasticity
    np.testing.assert_array_equal(out, net.apply(v, x, train=False))


def test_search_derive_train_end_to_end(tmp_path, nas_setup):
    """The reference's two-stage NAS flow (CI-script-fednas.sh:16-23:
    --stage search then --stage train): search a tiny supernet, extract the
    genotype, federatedly train the derived network built FROM it — with
    the auxiliary head and loss active (FedNASTrainer.py:179-183)."""
    import json

    from fedml_tpu.algorithms.fednas import FedNASTrainAPI

    data, api = nas_setup()
    api.run_round(0)
    geno = api.genotype()

    # genotype survives the json handoff (the file a search run records)
    p = tmp_path / "genotype.json"
    p.write_text(json.dumps(geno))

    data32 = synthetic_images(num_clients=2, image_shape=(32, 32, 3),
                              num_classes=3, samples_per_client=16,
                              test_samples=24, seed=0, size_lognormal=False)
    cfg = FedAvgConfig(comm_round=2, client_num_in_total=2,
                       client_num_per_round=2, epochs=1, batch_size=4,
                       lr=0.02, frequency_of_the_test=1, seed=0)
    t_api = FedNASTrainAPI(data32, cfg, genotype=str(p), layers=3,
                           init_filters=8, auxiliary=True,
                           auxiliary_weight=0.4, drop_path_prob=0.2)
    t_api.train()
    assert t_api.history and np.isfinite(t_api.history[-1]["test_loss"])
    # the aux head exists and trained params stayed finite
    flat = jax.tree.leaves(t_api.net.params)
    assert all(bool(jnp.isfinite(p_).all()) for p_ in flat)


def test_network_imagenet_forward():
    """NetworkImageNet (model.py:161): double stride-2 stem, cells start
    reduction_prev=True; train returns (logits, aux) like the CIFAR net."""
    from fedml_tpu.models.darts import NetworkImageNet

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64, 3))
    net = NetworkImageNet(genotype="DARTS_V2", num_classes=7, layers=3,
                          init_filters=8, auxiliary=False,
                          drop_path_prob=0.0)
    v = net.init(jax.random.PRNGKey(0), x, train=False)
    assert net.apply(v, x, train=False).shape == (2, 7)
    tr = net.apply(v, x, train=True,
                   rngs={"dropout": jax.random.PRNGKey(1)})
    assert tr.shape == (2, 7)  # bare logits without the aux head


def test_create_model_darts_derived_generic_task():
    """create_model('darts_cifar'/'darts_imagenet') returns a plain
    classifier (no aux tuple) usable by the generic classification_task —
    the derived nets ride every generic surface (CLI models, cross-process
    launch) like any other model."""
    from fedml_tpu.models import create_model

    net = create_model("darts_cifar", output_dim=3, layers=2,
                       init_filters=8, drop_path_prob=0.1)
    task = classification_task(net)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))
    y = jnp.array([0, 1])
    st = task.init(jax.random.PRNGKey(1), x)
    l, _, m = task.loss(st.params, st.extra, x, y, jnp.ones(2),
                        jax.random.PRNGKey(2), True)
    assert np.isfinite(float(l)) and float(m["count"]) == 2
    # imagenet variant resolves and evaluates too
    net_i = create_model("darts_imagenet", output_dim=4, layers=2,
                         init_filters=8, drop_path_prob=0.0)
    ti = classification_task(net_i)
    xi = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 32, 3))
    sti = ti.init(jax.random.PRNGKey(4), xi)
    assert ti.predict(sti.params, sti.extra, xi).shape == (1, 4)


def test_genotype_to_dot():
    """visualize.py analogue: DOT text with one labelled edge per gene
    entry and the concat fan-in."""
    from fedml_tpu.models.darts import GENOTYPES, genotype_to_dot

    dot = genotype_to_dot("FedNAS_V1", "normal")
    assert dot.startswith("digraph normal {") and dot.endswith("}")
    for op, _ in GENOTYPES["FedNAS_V1"]["normal"]:
        assert f'label="{op}"' in dot
    # 8 op edges + 4 concat edges
    assert dot.count(" -> ") == 12
    assert "digraph reduce" in genotype_to_dot("DARTS_V2", "reduce")


def test_aux_loss_term_active():
    """aux_classification_task: with the auxiliary head on, the training
    loss includes the weighted aux term (loss(aux_w=2) > loss(aux_w=0) on
    identical params/batch, both > 0)."""
    from fedml_tpu.core.tasks import aux_classification_task
    from fedml_tpu.models.darts import NetworkCIFAR

    # 32x32 input: the aux head expects 8x8 features at 2/3 depth
    # (model.py:66 "assuming input size 8x8"; layers=3 reduces twice)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 0])
    mask = jnp.ones(4)
    net = NetworkCIFAR(genotype="FedNAS_V1", num_classes=3, layers=3,
                       init_filters=8, auxiliary=True, drop_path_prob=0.0)
    t0 = aux_classification_task(net, aux_weight=0.0)
    t2 = aux_classification_task(net, aux_weight=2.0)
    st = t0.init(jax.random.PRNGKey(0), x)
    k = jax.random.PRNGKey(1)
    l0, _, m0 = t0.loss(st.params, st.extra, x, y, mask, k, True)
    l2, _, m2 = t2.loss(st.params, st.extra, x, y, mask, k, True)
    assert float(l2) > float(l0) > 0.0
    # metrics track the main head only — identical across aux weights
    assert float(m0["loss_sum"]) == float(m2["loss_sum"])
