"""FedNAS search, DARTS first and second order: one bilevel round on a tiny
supernet, the held-out split the alphas train on."""

import jax
import numpy as np


def test_fednas_search_round(nas_setup):
    _, api = nas_setup()
    a0 = jax.tree.map(np.copy,
                      {k: np.asarray(v) for k, v in api.net.params.items()
                       if k.startswith("alphas")})
    api.run_round(0)
    # both cell types' alphas moved (arch search active on each)
    assert not np.allclose(a0["alphas_normal"], api.net.params["alphas_normal"])
    assert not np.allclose(a0["alphas_reduce"], api.net.params["alphas_reduce"])
    assert len(api.genotype_history) == 1
    assert set(api.genotype_history[0]) == {
        "normal", "normal_concat", "reduce", "reduce_concat"}


def test_fednas_heldout_split_is_disjoint(nas_setup):
    """Without a per-client test split, the bilevel search must carve a
    DISJOINT val half out of each client's train data (the reference uses
    test_local as valid_queue; FedNASTrainer.py:34-50) — alphas never see
    the batches the weights train on."""
    data, api = nas_setup()
    for c in data.train_idx_map:
        w_idx = set(map(int, api.data.train_idx_map[c]))
        a_idx = set(map(int, api.data_a.train_idx_map[c]))
        assert w_idx and a_idx
        assert not (w_idx & a_idx)
        assert w_idx | a_idx == set(map(int, data.train_idx_map[c]))


def test_fednas_alphas_move_only_on_heldout_data(nas_setup):
    """With an EMPTY held-out stream the Architect step must be a no-op:
    alphas update exclusively from val batches."""
    data, api = nas_setup()
    # empty the alpha stream: no val samples for any client
    for c in api.data_a.train_idx_map:
        api.data_a.train_idx_map[c] = np.empty(0, np.int64)
    a0 = np.asarray(api.net.params["alphas_normal"]).copy()
    w_key = next(k for k in api.net.params if not k.startswith("alphas"))
    api.run_round(0)
    np.testing.assert_array_equal(a0, np.asarray(api.net.params["alphas_normal"]))
    # ...while the weights still trained on the train stream
    assert len(api.net.params[w_key])  # sanity: weights exist


def test_fednas_unrolled_second_order(nas_setup):
    """unrolled=True: the second-order Architect (exact autodiff through the
    inner SGD step, vs the reference's finite-difference approximation,
    architect.py:96-150) runs and moves the alphas."""
    _, api = nas_setup(unrolled=True)
    a0 = np.asarray(api.net.params["alphas_normal"]).copy()
    api.run_round(0)
    assert not np.allclose(a0, np.asarray(api.net.params["alphas_normal"]))
