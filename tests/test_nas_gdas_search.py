"""FedNAS search, GDAS variant: Gumbel straight-through selection and
staged tau annealing."""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models.darts import DARTSNetwork


def test_gdas_search_moves_alphas(nas_setup):
    """GDAS variant (model_search_gdas.py:1-188): Gumbel straight-through
    hard selection still carries gradient to BOTH alpha tensors, and eval
    (no gumbel noise) is deterministic."""
    _, api = nas_setup(nas_method="gdas", tau=5.0)
    a0 = {k: np.asarray(v).copy() for k, v in api.net.params.items()
          if k.startswith("alphas")}
    api.run_round(0)
    assert not np.allclose(a0["alphas_normal"],
                           api.net.params["alphas_normal"])
    assert not np.allclose(a0["alphas_reduce"],
                           api.net.params["alphas_reduce"])
    # eval-mode forward is deterministic (hard argmax, no noise)
    x = jnp.zeros((2, 12, 12, 3))
    mod = DARTSNetwork(num_classes=3, layers=2, init_filters=8,
                       nas_method="gdas")
    v = mod.init(jax.random.PRNGKey(0), x, train=False)
    np.testing.assert_array_equal(mod.apply(v, x, train=False),
                                  mod.apply(v, x, train=False))


def test_gdas_staged_tau_annealing(nas_setup):
    """The reference anneals tau per epoch (model_search_gdas set_tau);
    under jit the equivalent is STAGED search: params are tau-independent,
    so a fresh API at a lower tau continues from the previous stage's net
    (one recompile per stage)."""
    data, hot = nas_setup(nas_method="gdas", tau=10.0)
    hot.run_round(0)
    cold = nas_setup(nas_method="gdas", tau=1.0)[1]
    # carry the whole net (weights + alphas + extras) into the cold stage
    cold.net = hot.net
    a_before = np.asarray(cold.net.params["alphas_normal"]).copy()
    cold.run_round(1)
    assert not np.allclose(a_before, cold.net.params["alphas_normal"])
    assert set(cold.genotype()) == {"normal", "normal_concat",
                                    "reduce", "reduce_concat"}
    # tau is actually in effect. The straight-through PRIMAL is
    # tau-invariant by construction (hard one-hot + probs - stop_grad
    # (probs) == hard one-hot numerically; argmax(softmax(g/tau)) ==
    # argmax(g) for any tau) — tau shapes the GRADIENT through the soft
    # probs, so assert the alpha-gradients differ between temperatures on
    # the SAME params and rng.
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 12, 3))
    rng = {"dropout": jax.random.PRNGKey(7)}

    def alpha_grad(tau):
        mod = DARTSNetwork(num_classes=3, layers=2, init_filters=8,
                           nas_method="gdas", tau=tau)

        def loss(params):
            out = mod.apply({"params": params}, x, train=True, rngs=rng)
            return jnp.sum(out ** 2)

        return np.asarray(jax.grad(loss)(cold.net.params)["alphas_normal"])

    g_hot, g_cold = alpha_grad(10.0), alpha_grad(1.0)
    assert not np.allclose(g_hot, g_cold)
    # ...while the primal forward is identical across tau (hard selection)
    mod_h = DARTSNetwork(num_classes=3, layers=2, init_filters=8,
                         nas_method="gdas", tau=10.0)
    mod_c = DARTSNetwork(num_classes=3, layers=2, init_filters=8,
                         nas_method="gdas", tau=1.0)
    v = {"params": cold.net.params}
    np.testing.assert_allclose(
        np.asarray(mod_h.apply(v, x, train=True, rngs=rng)),
        np.asarray(mod_c.apply(v, x, train=True, rngs=rng)), atol=1e-5)
