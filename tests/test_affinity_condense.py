"""FedAvg-affinity tracking and dataset condensation (FedCon)."""

import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgConfig
from fedml_tpu.algorithms.fedavg_affinity import FedAvgAffinityAPI
from fedml_tpu.core.tasks import classification_task
from fedml_tpu.data.synthetic import synthetic_images
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.utils.condense import condense_dataset


def test_affinity_matrix_properties():
    data = synthetic_images(num_clients=4, image_shape=(10,), num_classes=4,
                            samples_per_client=40, test_samples=40, seed=0)
    task = classification_task(LogisticRegression(num_classes=4))
    cfg = FedAvgConfig(comm_round=2, client_num_in_total=4, client_num_per_round=4,
                       epochs=1, batch_size=8, lr=0.05, seed=0)
    api = FedAvgAffinityAPI(data, task, cfg)
    api.run_round(0)
    A = api.affinity_history[0]
    assert A.shape == (4, 4)
    np.testing.assert_allclose(np.diag(A), 1.0, atol=1e-5)  # self-similarity
    np.testing.assert_allclose(A, A.T, atol=1e-5)           # symmetry
    assert np.all(A <= 1.0 + 1e-5) and np.all(A >= -1.0 - 1e-5)


def test_condense_reduces_matching_loss():
    rng = np.random.RandomState(0)
    means = rng.normal(0, 2, (3, 12))
    y = rng.randint(0, 3, 300)
    x = (means[y] + rng.normal(0, 0.5, (300, 12))).astype(np.float32)
    task = classification_task(LogisticRegression(num_classes=3))
    xs, ys, losses = condense_dataset(task, x, y, num_classes=3,
                                      images_per_class=4, iters=20,
                                      syn_lr=0.05, batch_per_class=32)
    assert xs.shape == (12, 12) and ys.shape == (12,)
    assert losses[-1] < losses[0]  # gradient matching improves


def test_fedcon_trains_on_condensed_union():
    """FedCon (condense_api/fedcon_init_api parity): clients condense local
    data; the server trains on the sampled clients' synthetic union each
    round ('ce' and 'soft' types), moving the global model."""
    import jax
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.algorithms.fedcon import FedConAPI
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.synthetic import synthetic_images
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.utils.tree import tree_global_norm, tree_sub

    data = synthetic_images(num_clients=4, image_shape=(6, 6, 1), num_classes=3,
                            samples_per_client=30, test_samples=60, seed=0)
    task = classification_task(LogisticRegression(num_classes=3))
    cfg = FedAvgConfig(comm_round=2, client_num_in_total=4, client_num_per_round=2,
                       epochs=1, batch_size=10, lr=0.1, frequency_of_the_test=1)

    api = FedConAPI(data, task, cfg, images_per_class=2, condense_iters=5,
                    condense_steps=5, condense_train_type="ce", init_only=True)
    before = api.net
    api.run_round(0)
    assert len(api.syn_data) == 4  # every client condensed
    xs, ys, valid = api.syn_data[0]
    assert xs.shape[0] == ys.shape[0] == valid.shape[0] == 2 * 3  # ipc * classes
    assert 0 < float(valid.sum()) <= 2 * 3
    assert float(tree_global_norm(tree_sub(api.net.params, before.params))) > 1e-6
    assert api.last_condense_loss >= 0.0

    soft = FedConAPI(data, task, cfg, images_per_class=2, condense_iters=3,
                     condense_steps=4, condense_train_type="soft")
    soft.run_round(0)
    assert soft.last_condense_loss >= 0.0
    # soft training must MOVE params beyond the plain FedAvg aggregate: the
    # teacher is the pre-update global, so the KL gradient at the
    # post-aggregate student is nonzero (a teacher equal to the student
    # would silently no-op — regression cover)
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    plain = FedAvgAPI(data, task, cfg)
    plain.run_round(0)
    d = float(tree_global_norm(tree_sub(soft.net.params, plain.net.params)))
    assert d > 1e-8

    import pytest
    with pytest.raises(ValueError):
        FedConAPI(data, task, cfg, condense_train_type="nope")
