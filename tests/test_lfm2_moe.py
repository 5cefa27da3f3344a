"""``models/lfm2_moe.py`` against the plain reference
(``benchmark/reference/lfm2_24b_a2b_ep8.py``) at a small size on the CPU:
seeded weights, float32. The expert layer is told which experts it holds;
the shares add up to the uncut layer, and the exact path of full size
equals the budgeted one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_24b_a2b_ep8 as ref
from benchmark.tests.cells.tiny_lm import SMALL
from fedml_tpu.models import lfm2_moe
from fedml_tpu.models.lfm2_moe import Lfm2MoeLM

T = 32
# a budget no routing overflows, so that the budgeted path is what runs
ROOMY = {"moe_row_budget": 4.0}


def _tokens(seed=1, batch=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, T), 1,
                              SMALL["vocab_size"])


def _loss(logits, tokens):
    return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tokens[..., None], -1)[..., 0])


def _both(sizes, tokens, seed=3):
    """((loss, logits), gradients) of the model and of the reference."""
    init, forward = ref.make(sizes)
    params = init(jax.random.PRNGKey(seed))
    model = Lfm2MoeLM(**sizes)

    def of(apply):
        def fn(p):
            logits = apply(p, tokens)
            return _loss(logits, tokens), logits
        return jax.jit(jax.value_and_grad(fn, has_aux=True))(params)

    return of(lambda p, x: model.apply({"params": p}, x)), of(forward)


def _worst_leaf(got, want):
    gaps = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)
    return max(jax.tree.leaves(gaps))


LAYERS = {
    "all_six": dict(SMALL, **ROOMY),
    "dense_conv": dict(SMALL, layer_types=["conv"], num_dense_layers=1),
    "dense_attention": dict(SMALL, layer_types=["full_attention"],
                            num_dense_layers=1),
    "expert_conv": dict(SMALL, layer_types=["conv"], num_dense_layers=0,
                        **ROOMY),
    "expert_attention": dict(SMALL, layer_types=["full_attention"],
                             num_dense_layers=0, **ROOMY),
    "two_query_blocks_a_head_group": dict(
        SMALL, layer_types=["full_attention"], num_dense_layers=1,
        attention_query_block=8),
}


@pytest.mark.parametrize("name", LAYERS)
def test_model_equals_reference(name):
    """Logits, loss and every gradient leaf; each layer kind alone."""
    ((loss_m, logits_m), grad_m), ((loss_r, logits_r), grad_r) = _both(
        LAYERS[name], _tokens())
    np.testing.assert_allclose(logits_m, logits_r, atol=2e-6)
    assert abs(float(loss_m) - float(loss_r)) < 1e-6
    assert _worst_leaf(grad_m, grad_r) < 1e-5
    # the tree is the reference's, name for name
    assert jax.tree.structure(grad_m) == jax.tree.structure(grad_r)


def test_init_publishes_the_references_tree():
    init, _ = ref.make(SMALL)
    want = jax.tree.map(jnp.shape, init(jax.random.PRNGKey(0)))
    got = Lfm2MoeLM(**SMALL).init(jax.random.PRNGKey(0), _tokens())
    assert jax.tree.map(jnp.shape, got["params"]) == want


def _layer(sizes, held, budget, seed=5):
    """The expert layer alone on random hidden states, with the held
    experts' matrices cut from an uncut layer's."""
    sz = Lfm2MoeLM(**dict(sizes, experts_held=list(held),
                          moe_row_budget=budget)).sizes()
    d, f, e = sz.hidden_size, sz.moe_intermediate_size, sz.num_experts
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    full = {"router": jax.random.normal(ks[0], (d, e)) / 8,
            "expert_bias": 0.05 * jax.random.normal(ks[1], (e,)),
            "experts_w1": jax.random.normal(ks[2], (e, d, f)) / 8,
            "experts_w3": jax.random.normal(ks[3], (e, d, f)) / 8,
            "experts_w2": jax.random.normal(ks[4], (e, f, d)) / 7}
    m = jax.random.normal(ks[5], (96, d))
    cut = dict(full, **{k: v[held[0]:held[1]] for k, v in full.items()
                        if k.startswith("experts_")})
    return sz, full, cut, m


def test_the_shares_add_up():
    """The layer's output for each of the 8 shares of 2 experts in turn
    sums to the uncut reference's layer: nothing is counted twice and no
    token is dropped."""
    total, tokens = 0.0, 0.0
    for share in range(8):
        held = (2 * share, 2 * share + 2)
        sz, full, cut, m = _layer(SMALL, held, 0.5)
        f, stats = lfm2_moe.expert_layer(m, cut, sz)
        total, tokens = total + f, tokens + float(stats["rows_real"])
    uncut = dict(SMALL, head_dim=16, experts_held=[0, 16])
    want = ref.expert_layer(full, m, uncut)
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert tokens == 96 * SMALL["num_experts_per_tok"]


@pytest.mark.parametrize("held", [(0, 2), (6, 10), (0, 16)])
def test_overflow_path_equals_budgeted_path(held):
    """A budget the routing overflows sends the step down the exact path
    of full size: same output, same gradients, and it is counted."""
    outs = {}
    for name, budget in (("budgeted", 4.0), ("overflow", 0.01)):
        sz, _, cut, m = _layer(SMALL, held, budget)

        def fn(m, cut):
            f, stats = lfm2_moe.expert_layer(m, cut, sz)
            return jnp.sum(f * jnp.cos(f)), (f, stats)

        (_, (f, stats)), grads = jax.value_and_grad(
            fn, argnums=(0, 1), has_aux=True)(m, cut)
        outs[name] = (f, grads, stats)
    (f_b, g_b, st_b), (f_o, g_o, st_o) = outs["budgeted"], outs["overflow"]
    assert float(st_b["fallback_steps"]) == 0.0
    assert float(st_o["fallback_steps"]) == 1.0
    assert float(st_o["rows_dispatched"]) == (held[1] - held[0]) * 96
    assert float(st_b["rows_real"]) == float(st_o["rows_real"]) > 0
    np.testing.assert_allclose(f_b, f_o, atol=1e-5)
    assert _worst_leaf(g_b, g_o) < 1e-5


def test_bias_changes_who_is_selected_and_not_the_weights():
    sz, full, _, m = _layer(SMALL, (0, 16), 4.0)
    k, scale = sz.num_experts_per_tok, sz.routed_scaling_factor
    chosen, w = lfm2_moe.route(m, full["router"], full["expert_bias"], k,
                               scale)
    plain, w_plain = lfm2_moe.route(m, full["router"],
                                    jnp.zeros_like(full["expert_bias"]), k,
                                    scale)
    assert bool(jnp.any(jnp.sort(chosen, -1) != jnp.sort(plain, -1)))
    # where the same experts are chosen the weights are the same: the bias
    # is no part of them; they sum to one over the chosen
    same = jnp.all(chosen == plain, axis=-1)
    assert bool(jnp.any(same))
    np.testing.assert_allclose(w[same], w_plain[same], atol=1e-7)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-5)
    grad = jax.grad(lambda b: jnp.sum(lfm2_moe.route(
        m, full["router"], b, k, scale)[1] ** 2))(full["expert_bias"])
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_plan_rows_is_a_bijection_in_whole_tiles():
    chosen = jax.random.randint(jax.random.PRNGKey(0), (40, 2), 0, 16)
    dest, row_src, tile_expert, counts, padded = lfm2_moe.plan_rows(
        chosen, 4, 8, 64, 8)
    dest, row_src = np.asarray(dest), np.asarray(row_src)
    held = (np.asarray(chosen) >= 4) & (np.asarray(chosen) < 8)
    assert int(counts.sum()) == held.sum() and int(padded) % 8 == 0
    assert (dest[~held] == 64).all() and len(set(dest[held])) == held.sum()
    for row, src in enumerate(row_src):
        if src < 80:  # a real row: it is its assignment's, in its tile
            assert dest.reshape(-1)[src] == row
            assert np.asarray(chosen).reshape(-1)[src] - 4 \
                == int(tile_expert[row // 8])
    assert (row_src < 80).sum() == held.sum()


def test_stats_are_sown_once_a_call():
    model = Lfm2MoeLM(**SMALL)
    params = model.init(jax.random.PRNGKey(0), _tokens())["params"]
    _, sown = model.apply({"params": params}, _tokens(),
                          mutable=[lfm2_moe.STATS])
    stats = {k: v[0] for k, v in sown[lfm2_moe.STATS].items()}
    assert stats["expert_tokens"].shape == (4, 2)
    assert float(stats["rows_real"]) == float(stats["expert_tokens"].sum())
    assert float(stats["rows_dispatched"]) >= float(stats["rows_real"])
    # and nothing where the collection is not asked for
    assert Lfm2MoeLM(**SMALL).apply({"params": params}, _tokens()).shape \
        == (3, T, SMALL["vocab_size"])
