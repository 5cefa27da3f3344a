"""``models/lfm2_moe.py`` against the plain reference
(``benchmark/reference/lfm2_24b_a2b_ep8.py``) at a small size on the CPU:
seeded weights, float32. The expert layer is told which experts it holds;
the shares add up to the uncut layer, and the exact path of full size
equals the budgeted one."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_24b_a2b_ep8 as ref
from benchmark.tests.cells.tiny_lm import SMALL
from fedml_tpu.models import lfm2_moe, lm_layers
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
    chosen, w = lm_layers.route(m, full["router"], full["expert_bias"], k,
                               scale)
    plain, w_plain = lm_layers.route(m, full["router"],
                                    jnp.zeros_like(full["expert_bias"]), k,
                                    scale)
    assert bool(jnp.any(jnp.sort(chosen, -1) != jnp.sort(plain, -1)))
    # where the same experts are chosen the weights are the same: the bias
    # is no part of them; they sum to one over the chosen
    same = jnp.all(chosen == plain, axis=-1)
    assert bool(jnp.any(same))
    np.testing.assert_allclose(w[same], w_plain[same], atol=1e-7)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-5)
    grad = jax.grad(lambda b: jnp.sum(lm_layers.route(
        m, full["router"], b, k, scale)[1] ** 2))(full["expert_bias"])
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_plan_rows_is_a_bijection_in_whole_tiles():
    chosen = jax.random.randint(jax.random.PRNGKey(0), (40, 2), 0, 16)
    dest, row_src, tile_expert, counts, padded = lm_layers.plan_rows(
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


# ------------------------------------- the tiles past the last expert group
@jax.custom_vjp
def _every_tile(x, w1, w3, w2, tile_expert, n_used):
    """The tile loop as it was before it left the tiles past the last
    group out: every tile of the budget through its expert's products."""
    return _every_tile_fwd(x, w1, w3, w2, tile_expert, n_used)[0]


def _every_tile_fwd(x, w1, w3, w2, tile_expert, n_used):
    tiles = x.reshape(tile_expert.shape[0], -1, x.shape[-1])
    pick = lm_layers._pick

    def one(_, inp):
        xt, e = inp
        h1, h3 = xt @ pick(w1, e), xt @ pick(w3, e)
        return None, ((jax.nn.silu(h1) * h3) @ pick(w2, e), h1, h3)

    _, (y, h1, h3) = jax.lax.scan(one, None, (tiles, tile_expert))
    return y.reshape(x.shape), (tiles, h1, h3, w1, w3, w2, tile_expert)


def _every_tile_bwd(res, dy):
    tiles, h1, h3, w1, w3, w2, tile_expert = res
    pick, add_at = lm_layers._pick, lm_layers._add_at

    def one(acc, inp):
        dw1, dw3, dw2 = acc
        xt, h1t, h3t, dyt, e = inp
        sig = jax.nn.sigmoid(h1t)
        act = h1t * sig
        dw2 = add_at(dw2, e, (act * h3t).T @ dyt)
        dg = dyt @ pick(w2, e).T
        dh1 = dg * h3t * (sig * (1 + h1t * (1 - sig)))
        dh3 = dg * act
        dw1 = add_at(dw1, e, xt.T @ dh1)
        dw3 = add_at(dw3, e, xt.T @ dh3)
        dx = dh1 @ pick(w1, e).T + dh3 @ pick(w3, e).T
        return (dw1, dw3, dw2), dx

    zeros = tuple(jnp.zeros_like(w) for w in (w1, w3, w2))
    (dw1, dw3, dw2), dx = jax.lax.scan(
        one, zeros, (tiles, h1, h3, dy.reshape(tiles.shape), tile_expert))
    return dx.reshape(dy.shape), dw1, dw3, dw2, None, None


_every_tile.defvjp(_every_tile_fwd, _every_tile_bwd)


def _budgeted_every_tile(m, chosen, w, w1, w3, w2, plan):
    """``expert_layer``'s budgeted branch over ``_every_tile``: a function
    of its own, since ``lax.cond`` keeps a branch's trace by the branch."""
    dest, row_src, tile_expert, n_used = plan
    y = _every_tile(lm_layers.dispatch(m, dest, row_src), w1, w3, w2,
                    tile_expert, n_used)
    return lm_layers.combine(y, w, dest, row_src)

HELD = (0, 4)


def _routed(routing, seed=5):
    """The expert layer's sizes, held weights and hidden states under a
    budget that leaves tiles past the last group (``tail_empty``) or one
    whose tiles the groups fill to the last (``every_tile_used``); the
    rows the groups take, and the budget's."""
    sz, _, cut, m = _layer(SMALL, HELD, 4.0, seed)
    chosen, _ = lm_layers.route(m, cut["router"], cut["expert_bias"],
                                sz.num_experts_per_tok,
                                sz.routed_scaling_factor)
    padded = int(lm_layers.plan_rows(chosen, *HELD, 4096,
                                     sz.moe_tile_rows)[-1])
    if routing == "every_tile_used":
        sz = _layer(SMALL, HELD, (padded - 0.5) / m.shape[0], seed)[0]
    return sz, cut, m, padded, lm_layers.budget_rows(sz, m.shape[0])


def _layer_and_grads(sz, fold):
    def fn(m, cut):
        f, stats = lm_layers.expert_layer(m, cut, sz)
        return jnp.sum(f * jnp.cos(f)), (f, stats)

    run = jax.value_and_grad(fn, argnums=(0, 1), has_aux=True)
    return jax.jit(jax.vmap(run, in_axes=(0, None)) if fold == "vmap"
                   else run)


@pytest.mark.parametrize("fold", ["jit", "vmap"])
@pytest.mark.parametrize("routing", ["tail_empty", "every_tile_used"])
def test_tiles_past_the_groups_change_no_bit(routing, fold, monkeypatch):
    """The loop runs the tiles that hold rows and gives the rest zeros:
    the layer's output and every gradient are the loop over every tile's,
    bit for bit, under ``jit`` and under ``vmap`` over two clients (where
    the ``cond`` is a ``select`` and both branches run)."""
    sz, cut, m, padded, rows = _routed(routing)
    assert (padded < rows) == (routing == "tail_empty")
    if fold == "vmap":
        m = jnp.stack([m, _routed(routing, seed=6)[2]])
    (_, (f, stats)), grads = _layer_and_grads(sz, fold)(m, cut)
    monkeypatch.setattr(lm_layers, "_budgeted", _budgeted_every_tile)
    (_, (f_all, stats_all)), grads_all = _layer_and_grads(sz, fold)(m, cut)
    assert float(jnp.max(stats["fallback_steps"])) == 0.0
    assert np.array_equal(f, f_all)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_all)):
        assert np.array_equal(got, want)
    assert np.array_equal(stats["rows_real"], stats_all["rows_real"])


@pytest.mark.parametrize("routing", ["tail_empty", "every_tile_used"])
def test_rows_dispatched_are_the_rows_the_groups_take(routing):
    """On the budgeted path the counter reads the rows the loop computes,
    ``padded_rows`` of the plan: under the budget where tiles are left
    empty, the budget where the groups fill it."""
    sz, cut, m, padded, rows = _routed(routing)
    stats = jax.jit(lambda m: lm_layers.expert_layer(m, cut, sz)[1])(m)
    assert float(stats["fallback_steps"]) == 0.0
    assert float(stats["rows_dispatched"]) == padded
    assert (padded < rows) == (routing == "tail_empty")


def test_tile_loop_updates_the_expert_gradients_in_place():
    """The backward loop's ``cond`` updates the experts' gradient
    accumulators in one branch and passes them through in the other: the
    compiled loop makes no copy of an ``[E, D, F]`` buffer inside any
    computation but the entry (CPU; the chip's compiler, read for a
    described v5e at the cells' shapes, makes none either)."""
    e, d, f, tile, n = 4, 40, 24, 8, 6
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w1, w3 = (jax.random.normal(k, (e, d, f)) for k in ks[:2])
    w2, x = jax.random.normal(ks[2], (e, f, d)), jax.random.normal(
        ks[3], (n * tile, d))
    tile_expert = jnp.minimum(jnp.arange(n), e - 1).astype(jnp.int32)
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
        lm_layers.tiled_experts(*a, tile_expert, jnp.int32(4)))),
        (0, 1, 2, 3)))
    text = grad.lower(x, w1, w3, w2).compile().as_text()
    assert " conditional(" in text
    computation, copies = None, []
    for line in text.splitlines():
        if line and not line.startswith(" "):
            computation = line.split()[0]
        elif (" copy(" in line and computation != "ENTRY"
              and any(f"f32[{s}]" in line.split("copy(")[0]
                      for s in (f"{e},{d},{f}", f"{e},{f},{d}"))):
            copies.append(line.strip())
    assert not copies


# ---------------------------------------------- what a block keeps of its pass
# one short convolution with the dense layer, one attention layer with
# experts, on the budgeted path
TWO = dict(SMALL, layer_types=["conv", "full_attention"], num_dense_layers=1,
           **ROOMY)
BUDGETS = {"zero": 0, "the_constant": lfm2_moe.KEPT_BYTES,
           "unbounded": float("inf")}


def _grads(sizes, monkeypatch, budget):
    monkeypatch.setattr(lfm2_moe, "KEPT_BYTES", budget)
    model, tokens = Lfm2MoeLM(**sizes), _tokens()
    params = ref.make(sizes)[0](jax.random.PRNGKey(3))
    return jax.jit(jax.value_and_grad(lambda p: _loss(
        model.apply({"params": p}, tokens), tokens)))(params)


@pytest.mark.parametrize("budget", BUDGETS)
def test_kept_outputs_leave_loss_and_gradients_as_they_were(budget,
                                                            monkeypatch):
    """A kept output and one made again are the same numbers: whatever the
    budget keeps, the loss and every gradient leaf are those of the block
    that keeps its input alone, and the reference's."""
    (_, grad_r), _ = _both(TWO, _tokens())[::-1]
    loss_0, grad_0 = _grads(TWO, monkeypatch, 0)
    loss, grad = _grads(TWO, monkeypatch, BUDGETS[budget])
    assert abs(float(loss) - float(loss_0)) < 1e-6
    assert _worst_leaf(grad, grad_0) < 1e-6
    assert _worst_leaf(grad, grad_r) < 1e-5


def _block(kind, dense, sizes=TWO, batch=3):
    """One block, its weights and an input; the names of its outputs with
    the shapes they have there."""
    sz = Lfm2MoeLM(**sizes).sizes()
    h = jax.random.normal(jax.random.PRNGKey(0), (batch, T, sz.hidden_size))
    block = lfm2_moe.Lfm2Block(sz, kind, dense)
    d, hd, f = sz.hidden_size, sz.head_dim, sz.moe_intermediate_size
    nq, nkv = sz.num_attention_heads, sz.num_key_value_heads
    rows, tile = lm_layers.budget_rows(sz, batch * T), sz.moe_tile_rows
    shapes = {
        "conv_in": (batch, T, 3 * d), "conv_out": (batch, T, d),
        "attn_q": (batch, T, nq * hd), "attn_k": (batch, T, nkv * hd),
        "attn_v": (batch, T, nkv * hd), "attn_out": (batch, T, d),
        "attn_context": (batch, T, nkv, nq // nkv, hd),
        "mlp_h1": (batch, T, sz.intermediate_size),
        "mlp_h3": (batch, T, sz.intermediate_size),
        "moe_chosen": (batch * T, sz.num_experts_per_tok),
        "moe_tiles": (rows // tile, tile, d), "moe_h1": (rows // tile, tile, f),
        "moe_h3": (rows // tile, tile, f), "moe_y": (rows, d)}
    entries = lfm2_moe.block_outputs(sz, kind, dense, batch, T, 4)
    return block, block.init(jax.random.PRNGKey(1), h), h, entries, shapes


def _residuals(block, params, h, policy):
    """(shapes kept that are no argument of the block, the gradient)."""
    from jax._src.ad_checkpoint import saved_residuals

    fn = jax.checkpoint(lambda p, x: jnp.sum(jnp.sin(block.apply(p, x)[0])),
                        policy=policy)
    # integers that jax derives from a kept one (``take_along_axis``'s
    # indices from the experts chosen) ride along unnamed: not counted
    kept = [aval.shape for aval, why in saved_residuals(fn, params, h)
            if "from the argument" not in why and "constant" not in why
            and (jnp.issubdtype(aval.dtype, jnp.floating) or "named" in why)]
    return sorted(kept), jax.jit(jax.grad(fn))(params, h)


KINDS = {"conv_dense": ("conv", True), "attention_experts":
         ("full_attention", False)}


@pytest.mark.parametrize("kind", KINDS)
def test_an_empty_budget_keeps_the_blocks_input_alone(kind):
    """No name kept: the residuals are the block's arguments, as under a
    ``jax.checkpoint`` with no policy, and the gradient is that one's bit
    for bit."""
    block, params, h, _, _ = _block(*KINDS[kind])
    kept_0, grad_0 = _residuals(
        block, params, h, jax.checkpoint_policies.save_only_these_names())
    kept_none, grad_none = _residuals(block, params, h, None)
    assert kept_0 == kept_none == []
    for a, b in zip(jax.tree.leaves(grad_0), jax.tree.leaves(grad_none)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_an_empty_budget_traces_the_model_without_a_policy(monkeypatch):
    """The whole model at budget zero against ``nn.remat`` with no policy
    (the call before the rule): bit for bit."""
    loss_0, grad_0 = _grads(TWO, monkeypatch, 0)
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    loss_none, grad_none = _grads(TWO, monkeypatch, 0)
    assert float(loss_0) == float(loss_none)
    for a, b in zip(jax.tree.leaves(grad_0), jax.tree.leaves(grad_none)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", KINDS)
def test_a_block_keeps_each_kept_name_and_none_of_the_others(kind):
    """Entry by entry of ``block_outputs``: with the first n kept, what the
    block saves beside its arguments has their shapes and no other; an
    entry's bytes are its names' shapes."""
    block, params, h, entries, shapes = _block(*KINDS[kind])
    for names, nbytes, _ in entries:
        assert nbytes == 4 * sum(math.prod(shapes[n]) for n in names)
    for n in range(len(entries) + 1):
        names = [name for e in entries[:n] for name in e[0]]
        kept, _ = _residuals(
            block, params, h,
            jax.checkpoint_policies.save_only_these_names(*names))
        assert kept == sorted(shapes[name] for name in names), names


def test_the_full_size_path_keeps_nothing_stacked_over_the_experts():
    """``gated_mlp`` is the full-size path's body too, run once for each
    held expert: a name inside it would keep its outputs ``held`` times
    over, on the path not taken as well. An expert block whose budget its
    tiles overflow, every name kept: no residual is stacked over the held
    experts."""
    sizes = dict(TWO, moe_row_budget=0.01)
    block, params, h, entries, shapes = _block("conv", False, sizes)
    held = sizes["experts_held"][1] - sizes["experts_held"][0]
    _, stats = block.apply(params, h)
    assert float(stats["fallback_steps"]) == 1.0
    every = [name for e in entries for name in e[0]]
    kept, _ = _residuals(block, params, h,
                         jax.checkpoint_policies.save_only_these_names(
                             *every, "mlp_h1", "mlp_h3"))
    assert kept == sorted(shapes[name] for name in every)
    assert not [s for s in kept if s[:2] == (held, h.shape[0] * T)]


def _cell_sizes():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2_24b_a2b_ep8.json")) as f:
        return json.load(f)["model"]["kwargs"]


def _kept(model, batch, seq_len, budget=None):
    plan, nbytes = lfm2_moe.kept_names(
        model.sizes(), model.layer_types, model.num_dense_layers, batch,
        seq_len, 4, lfm2_moe.KEPT_BYTES if budget is None else budget)
    return {(i, name) for i, layer in enumerate(plan)
            for name, kept in layer.items() if kept}, nbytes


@pytest.mark.parametrize("seq_len", [512, 2048, 8192])
def test_kept_names_a_larger_step_keeps_no_more(seq_len):
    """At the cell's widths, batch after batch: what a larger step keeps a
    smaller one kept, its bytes are inside the constant, a small step
    keeps every name and a large one none."""
    model = Lfm2MoeLM(**_cell_sizes())
    every, _ = _kept(model, 1, seq_len, float("inf"))
    assert len(every) == 2 * 4 + 5 + 2 + 4 * 5
    was = every
    for batch in (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 64, 256, 1 << 14):
        now, nbytes = _kept(model, batch, seq_len)
        assert now <= was and nbytes <= lfm2_moe.KEPT_BYTES
        was = now
    assert _kept(model, 1, 512)[0] == every and not was


def test_kept_names_at_the_cells_step():
    """16,384 tokens a step: every product's output is kept, the expert
    layers' dispatched rows (a gather, no product: the last in rank) are
    not, and longer sequences at the cell's batch keep no more."""
    model = Lfm2MoeLM(**_cell_sizes())
    kept, nbytes = _kept(model, 8, 2048)
    assert nbytes == 5_168_431_104 <= lfm2_moe.KEPT_BYTES
    every, _ = _kept(model, 8, 2048, float("inf"))
    assert every - kept == {(i, "moe_tiles") for i in (1, 2, 3, 4)}
    was = _kept(model, 8, 256)[0]
    for seq_len in (512, 1024, 1536, 2048, 3072, 4096, 8192, 32768):
        now = _kept(model, 8, seq_len)[0]
        assert now <= was, seq_len
        was = now


def _plan_as_the_model_ranked_it(sz, layer_types, num_dense_layers, batch,
                                 seq_len, budget):
    """The rule as ``lfm2_moe.kept_names`` wrote it out before it moved to
    ``lm_layers``, kept here word for word to hold the move to it."""
    ranked = []
    for layer, kind in enumerate(layer_types):
        for names, nbytes, ops in lfm2_moe.block_outputs(
                sz, kind, layer < num_dense_layers, batch, seq_len, 4):
            ranked.append((-ops / nbytes, nbytes, layer, names))
    plan = [{} for _ in layer_types]
    total, fits = 0, True
    for _, nbytes, layer, names in sorted(ranked):
        fits = fits and total + nbytes <= budget
        total += nbytes if fits else 0
        plan[layer].update(dict.fromkeys(names, fits))
    return plan, total


@pytest.mark.parametrize("seq_len", [512, 2048, 8192])
def test_the_shared_rule_gives_the_plan_it_gave_before_the_move(seq_len):
    """At the cell's widths, over batches and budgets from nothing to
    everything (the cell's step and constant among them): the same plan,
    name for name and in the same order, and the same bytes."""
    model = Lfm2MoeLM(**_cell_sizes())
    args = (model.sizes(), model.layer_types, model.num_dense_layers)
    for batch in (1, 8, 64):
        for budget in (0, 1e8, 1e9, lfm2_moe.KEPT_BYTES, 2e10,
                       float("inf")):
            plan, nbytes = lfm2_moe.kept_names(*args, batch, seq_len, 4,
                                               budget)
            want, want_bytes = _plan_as_the_model_ranked_it(
                *args, batch, seq_len, budget)
            assert [list(layer.items()) for layer in plan] \
                == [list(layer.items()) for layer in want]
            assert nbytes == want_bytes


def test_kept_tile_outputs_keep_the_choice_that_laid_them_out():
    """The rows of a kept ``moe_h1`` lie where the forward pass's top-k put
    them. A backward pass that chose again (from hidden states made again,
    which a compiler may round otherwise) could break a near tie the other
    way and shift every later row of that expert's group: with the tiles'
    outputs kept the experts chosen are kept too and top-k runs once; with
    nothing kept the whole block, its choice included, is made again."""
    block, params, h, entries, _ = _block("full_attention", False)
    group = next(names for names, _, _ in entries if "moe_h1" in names)
    assert "moe_chosen" in group

    def top_ks(names):
        fn = jax.checkpoint(
            lambda p, x: jnp.sum(jnp.sin(block.apply(p, x)[0])),
            policy=jax.checkpoint_policies.save_only_these_names(*names))
        return str(jax.make_jaxpr(jax.grad(fn))(params, h)).count("top_k")

    assert top_ks(()) == 2 and top_ks(group) == 1
