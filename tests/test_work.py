"""The round program's count of its own matrix work (obs/work.py): which
pass each product belongs to, proved on a toy with one rematerialised
region; convolutions at the shapes they are issued; and the language
models' gradients at the tiny cells' sizes, against the walk the
benchmark's tests pin and against the counts by hand.

CPU, small shapes: jaxprs only, nothing is compiled."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from benchmark.tests.test_qwen3_next_cell import _jaxpr_flops
from fedml_tpu.obs import work


def _by_run(ops: dict) -> dict:
    out = dict.fromkeys(work.RUNS, 0.0)
    for (_, run, flops, _), n in ops.items():
        out[run] += flops * n
    return out


# ------------------------------------------------------- the passes, a toy
@pytest.mark.parametrize("remat", [True, False])
def test_one_checkpoint_region_makes_its_forward_again(remat):
    """A region of two products and a scan of three steps, under a scope,
    rematerialised or not, inside a scan over two silos as the fold runs
    it: made again in the backward pass are exactly the region's forward
    products, and nothing without the checkpoint; the gradients are two a
    product, but for those whose result nothing reads."""
    d, t = 8, 4

    def region(w, h):
        with jax.named_scope("fed_region"):
            h = jnp.tanh(h @ w)

            def step(c, _):
                return jnp.sin(c @ w), None

            return lax.scan(step, h, None, length=3)[0]

    def loss(w, head, x):
        h = (jax.checkpoint(region) if remat else region)(w, x)
        return jnp.sum(h @ head)

    def silos(w, head, xs):
        with jax.named_scope("fed_client_fold"):
            return lax.scan(lambda c, x: (c, jax.grad(loss, (0, 1))(w, head,
                                                                   x)),
                            0.0, xs)[1]

    ops = work.matmul_work(jax.make_jaxpr(silos)(
        jnp.ones((d, d)), jnp.ones((d, 1)), jnp.ones((2, t, d))))
    one = 2 * t * d * d  # one product of the region
    runs = _by_run(ops)
    # two silos; the head's forward product is dead (the loss is not
    # returned), and so is the gradient of the region's input x
    assert runs["forward"] == 2 * 4 * one
    assert runs["recompute"] == (2 * 4 * one if remat else 0)
    assert runs["gradient"] == 2 * (7 * one + 2 * 2 * t * d)
    scopes = {scope for scope, run, *_ in ops if run == "recompute"}
    assert scopes == ({"fed_region"} if remat else set())
    # a product whose result nothing reads is not counted: w's gradient of
    # the head alone, with the region's inputs' gradients dropped
    head_only = work.matmul_work(jax.make_jaxpr(jax.grad(loss, 1))(
        jnp.ones((d, d)), jnp.ones((d, 1)), jnp.ones((t, d))))
    assert _by_run(head_only)["gradient"] == 2 * t * d
    assert _by_run(head_only)["recompute"] == 0


# ------------------------------------------------------------ convolutions
def test_convolution_with_feature_groups_counts_what_runs():
    """A vmapped convolution is one call with feature groups: its FLOPs are
    every group's products, and its gradients (the kernel's by batch
    groups) as many again each; bytes are operands and result."""
    n, h, w, c, g = 2, 6, 6, 3, 4

    def conv(x, k):
        return lax.conv_general_dilated(
            x, k, (1, 1), "SAME", feature_group_count=g,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    x, k = jnp.ones((n, h, w, g * c)), jnp.ones((3, 3, c, g * 5))
    fwd = work.matmul_work(jax.make_jaxpr(conv)(x, k))
    macs = n * h * w * g * 5 * 9 * c
    nbytes = 4 * (x.size + k.size + n * h * w * g * 5)
    assert fwd == {(work.OUTSIDE, "forward", 2 * macs, nbytes): 1.0}
    grad = work.matmul_work(jax.make_jaxpr(jax.grad(
        lambda x, k: jnp.sum(jnp.sin(conv(x, k))), (0, 1)))(x, k))
    assert _by_run(grad) == {"forward": 2 * macs, "recompute": 0.0,
                             "gradient": 4 * macs}


def test_width_packed_site_counts_its_zeros(monkeypatch):
    """The kernel gradient of a width-packed site is issued as one
    convolution with a ``[3, P+2, Cin, P*Cout]`` kernel at stride ``(1,
    P)``: (P+2)/3 times the plain products, the rest exact zeros, all under
    ``fed_conv_packed``."""
    from fedml_tpu.ops import packed_conv

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, h, w, c = 2, 4, 8, 16
    p = packed_conv.pack_factor((3, 3, c, c), (1, 1), w, "tpu", grad=True)
    assert p == 8

    def loss(k, x):
        return jnp.sum(jnp.tanh(packed_conv.conv_general_dilated(
            x, k, (1, 1), "SAME", dimension_numbers=packed_conv._NHWC)))

    ops = work.matmul_work(jax.make_jaxpr(jax.grad(loss))(
        jnp.ones((3, 3, c, c)), jnp.ones((b, h, w, c))))
    plain = 2 * b * h * w * 9 * c * c
    assert {(s, r, f) for s, r, f, _ in ops} == {
        (packed_conv.SCOPE, "forward", plain),
        (packed_conv.SCOPE, "gradient", plain * (p + 2) // 3)}
    assert set(ops.values()) == {1.0}


# ---------------------------------------------------- the language models
def _lm(which):
    if which == "qwen3_next":
        from benchmark.counts import qwen3_next_80b_a3b_ep16 as counts
        from benchmark.reference import qwen3_next_80b_a3b_ep16 as ref
        from benchmark.tests.cells.tiny_qwen3_next import SEQ_LEN, SMALL
        from fedml_tpu.models import qwen3_next as mod
        return mod, mod.Qwen3NextLM, ref, counts, SMALL, SEQ_LEN
    from benchmark.counts import lfm2_24b_a2b_ep8 as counts
    from benchmark.reference import lfm2_24b_a2b_ep8 as ref
    from benchmark.tests.cells.tiny_lm import SEQ_LEN, SMALL
    from fedml_tpu.models import lfm2_moe as mod
    return mod, mod.Lfm2MoeLM, ref, counts, SMALL, SEQ_LEN


@functools.lru_cache(maxsize=8)
def _gradient(which, budget=None, kind=None):
    """The gradient jaxpr of one tiny language model at a kept budget
    (the module's own where None)."""
    mod, cls, ref, _, small, seq_len = _lm(which)
    sizes = dict(small, layer_types=[kind]) if kind else dict(small)
    model = cls(**sizes)
    tokens = jnp.ones((2, seq_len), jnp.int32)
    params = ref.make(sizes)[0](jax.random.PRNGKey(0))
    was = mod.KEPT_BYTES
    mod.KEPT_BYTES = was if budget is None else budget
    try:
        return jax.make_jaxpr(jax.grad(lambda p: jnp.mean(jax.nn.logsumexp(
            model.apply({"params": p}, tokens), -1))))(params), model, sizes
    finally:
        mod.KEPT_BYTES = was


def test_walk_equals_the_gradients_own_products():
    """The walk's FLOPs are ``_jaxpr_flops``' on the same gradient jaxpr
    (scan bodies as often as they loop, a ``cond`` by its last branch),
    with the blocks keeping their input alone. The LFM2 hand count is not
    the program's there (PERF.md section 5 has the difference)."""
    grad = _gradient("lfm2_moe", 0)[0]
    assert sum(_by_run(work.matmul_work(grad)).values()) \
        == _jaxpr_flops(grad.jaxpr)


@pytest.mark.parametrize("kind", ["linear_attention", "full_attention"])
def test_kept_outputs_take_their_operations_off_the_recompute(kind):
    """With the blocks keeping their input alone the walk is the hand
    count's and ``_jaxpr_flops``'; keeping every named output takes exactly
    ``block_outputs``' figures off the products made again, and leaves the
    forward and gradient products as they were."""
    from fedml_tpu.models import qwen3_next

    _, _, _, counts, small, seq_len = _lm("qwen3_next")
    none, model, sizes = _gradient("qwen3_next", 0, kind)
    every = _gradient("qwen3_next", float("inf"), kind)[0]
    a, b = (_by_run(work.matmul_work(j)) for j in (none, every))
    hand = sum(f for _, f, _ in counts.matmul_ops_per_step(2, sizes, seq_len))
    assert sum(a.values()) == _jaxpr_flops(none.jaxpr) == hand
    assert sum(b.values()) == _jaxpr_flops(every.jaxpr)
    entries = qwen3_next.block_outputs(model.sizes(), kind, 2, seq_len, 4)
    assert a["recompute"] - b["recompute"] == sum(o for _, _, o in entries)
    assert (a["forward"], a["gradient"]) == (b["forward"], b["gradient"])


def test_tile_products_count_at_the_budget():
    """The tile loop runs the tiles that hold rows behind a ``cond`` whose
    last branch is the products: the walk counts the tiles' three forward
    and six gradient products over every tile of the row budget, as it did
    when the loop ran every tile, and makes none of them again."""
    from fedml_tpu.models import lm_layers

    grad, model, sizes = _gradient("lfm2_moe")
    sz = model.sizes()
    experts = len(sizes["layer_types"]) - sizes["num_dense_layers"]
    rows = lm_layers.budget_rows(sz, 2 * _lm("lfm2_moe")[-1])
    assert rows > sz.moe_tile_rows
    one = 2 * rows * sz.hidden_size * sz.moe_intermediate_size
    runs = dict.fromkeys(work.RUNS, 0.0)
    for (scope, run, flops, _), n in work.matmul_work(grad).items():
        if scope == "fed_moe_experts":
            runs[run] += flops * n
    assert runs == {"forward": experts * 3 * one, "recompute": 0.0,
                    "gradient": experts * 6 * one}
