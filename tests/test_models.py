"""Model zoo shape/param checks (the reference's only unit test is a CNN
shape check, model/cv/test_cnn.py — we cover every family)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.local import NetState
from fedml_tpu.core.tasks import classification_task, sequence_task
from fedml_tpu.models import create_model
from fedml_tpu.models.cnn import CNNOriginalFedAvg
from fedml_tpu.models.gkt import GKTClientExtractor, GKTClientHead, GKTServerModel
from fedml_tpu.utils.tree import tree_size


def _init_apply(module, x):
    task = classification_task(module)
    net = task.init(jax.random.PRNGKey(0), x)
    out = task.predict(net.params, net.extra, x)
    return net, out


def test_cnn_original_param_count():
    """Reference cnn.py:26-97 reports 1,663,370 params (10-class head)."""
    x = jnp.zeros((2, 28, 28, 1))
    net, out = _init_apply(CNNOriginalFedAvg(only_digits=True), x)
    assert out.shape == (2, 10)
    assert tree_size(net.params) == 1_663_370
    net62, out62 = _init_apply(CNNOriginalFedAvg(only_digits=False), x)
    assert out62.shape == (2, 62)


@pytest.mark.parametrize("name,shape,classes", [
    ("lr", (2, 28, 28, 1), 10),
    ("cnn_dropout", (2, 28, 28, 1), 10),
    ("resnet56", (2, 32, 32, 3), 10),
    ("resnet18_gn", (2, 24, 24, 3), 100),
    ("mobilenet", (2, 32, 32, 3), 10),
    ("vgg11", (2, 32, 32, 3), 10),
])
def test_model_forward_shapes(name, shape, classes):
    x = jnp.zeros(shape)
    net, out = _init_apply(create_model(name, output_dim=classes), x)
    assert out.shape == (shape[0], classes)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("name,shape,classes", [
    ("mobilenet_v3", (2, 32, 32, 3), 10),
    ("mobilenet_v3_large", (2, 32, 32, 3), 10),
    ("efficientnet", (2, 32, 32, 3), 10),
])
def test_big_model_forward_shapes(name, shape, classes):
    x = jnp.zeros(shape)
    net, out = _init_apply(create_model(name, output_dim=classes), x)
    assert out.shape == (shape[0], classes)


def test_rnn_shapes():
    x = jnp.zeros((3, 80), jnp.int32)
    task = sequence_task(create_model("rnn", output_dim=90))
    net = task.init(jax.random.PRNGKey(0), x)
    out = task.predict(net.params, net.extra, x)
    assert out.shape == (3, 80, 90)


def test_rnn_stackoverflow_shapes():
    x = jnp.zeros((2, 20), jnp.int32)
    task = sequence_task(create_model("rnn_stackoverflow"))
    net = task.init(jax.random.PRNGKey(0), x)
    out = task.predict(net.params, net.extra, x)
    assert out.shape == (2, 20, 10004)


def test_gkt_split_pipeline():
    x = jnp.zeros((2, 32, 32, 3))
    ext = GKTClientExtractor()
    ev = ext.init(jax.random.PRNGKey(0), x, train=False)
    feats = ext.apply(ev, x, train=False)
    assert feats.shape == (2, 32, 32, 16)
    head = GKTClientHead(num_classes=10)
    hv = head.init(jax.random.PRNGKey(1), feats, train=False)
    assert head.apply(hv, feats, train=False).shape == (2, 10)
    srv = GKTServerModel(num_classes=10)
    sv = srv.init(jax.random.PRNGKey(2), feats, train=False)
    assert srv.apply(sv, feats, train=False).shape == (2, 10)


def test_batchnorm_models_train_in_fedavg():
    """BN models must work through the round engine: batch_stats live in
    'extra' and are federated-averaged."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.data.synthetic import synthetic_images

    data = synthetic_images(num_clients=4, image_shape=(16, 16, 3),
                            num_classes=4, samples_per_client=24,
                            test_samples=32, seed=0, size_lognormal=False)
    task = classification_task(create_model("resnet56", output_dim=4))
    cfg = FedAvgConfig(comm_round=1, client_num_in_total=4,
                       client_num_per_round=4, epochs=1, batch_size=8, lr=0.05)
    api = FedAvgAPI(data, task, cfg)
    assert "batch_stats" in api.net.extra
    before = jax.tree.leaves(api.net.extra)[0].copy()
    api.run_round(0)
    after = jax.tree.leaves(api.net.extra)[0]
    assert not np.allclose(np.asarray(before), np.asarray(after))


def test_resnet_bf16_compute_dtype():
    """Cross-silo HBM knob (both GN and BN variants): dtype=bfloat16 keeps
    PARAMS and norm scales f32, returns f32 logits, trains finite through
    the engine with remat on — the combination the cross-silo cell can
    use if the full-precision 10-client program doesn't fit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.resnet import ResNetCIFAR

    for norm in ("group", "batch", "none"):
        m = ResNetCIFAR(depth=8, num_classes=10, norm_type=norm,
                        dtype=jnp.bfloat16)
        x = jnp.zeros((2, 32, 32, 3), jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(v))
        out = m.apply(v, x, train=False)
        assert out.dtype == jnp.float32

    data = synthetic_images(num_clients=4, image_shape=(32, 32, 3),
                            num_classes=10, samples_per_client=8,
                            test_samples=16, seed=0, size_lognormal=False)
    cfg = FedAvgConfig(comm_round=1, client_num_in_total=4,
                       client_num_per_round=2, epochs=1, batch_size=4,
                       lr=0.1, remat=True)
    api = FedAvgAPI(data, classification_task(
        ResNetCIFAR(depth=8, num_classes=10, norm_type="group",
                    dtype=jnp.bfloat16)), cfg)
    metrics = api.run_round(0)
    assert np.isfinite(float(metrics["loss_sum"]))
    assert all(np.isfinite(np.asarray(p)).all()
               for p in jax.tree.leaves(jax.device_get(api.net.params)))


def test_cnn_bf16_compute_dtype():
    """dtype=bfloat16 keeps PARAMS f32 (mixed precision: bf16 is the
    activation/matmul dtype for the MXU), returns f32 logits, and trains
    to finite values through the engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.cnn import CNNOriginalFedAvg

    m = CNNOriginalFedAvg(only_digits=True, dtype=jnp.bfloat16)
    x = jnp.zeros((2, 28, 28, 1), jnp.float32)
    v = m.init(jax.random.PRNGKey(0), x)
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(v))
    assert m.apply(v, x).dtype == jnp.float32

    data = synthetic_images(num_clients=4, image_shape=(28, 28, 1),
                            num_classes=10, samples_per_client=8,
                            test_samples=16, seed=0, size_lognormal=False)
    cfg = FedAvgConfig(comm_round=1, client_num_in_total=4,
                       client_num_per_round=2, epochs=1, batch_size=4, lr=0.1)
    api = FedAvgAPI(data, classification_task(
        CNNOriginalFedAvg(only_digits=True, dtype=jnp.bfloat16)), cfg)
    metrics = api.run_round(0)
    assert np.isfinite(float(metrics["loss_sum"]))
    assert all(np.isfinite(np.asarray(p)).all()
               for p in jax.tree.leaves(jax.device_get(api.net.params)))
