"""The width-packed 3x3 convolution (ops/packed_conv.py) is the plain one:
output and both gradients to float32 rounding, alone and under ``vmap`` over
ten kernels, with the kernel gradient taken either way round (the saved
input or the incoming gradient laid out again); ``pack_factor`` and
``grad_lays_out`` are the whole rule; the CIFAR ResNet keeps its parameter
tree and, with the packed path forced through ``nn.Conv``'s
``conv_general_dilated=`` hook, its logits and gradient under each norm.

The rule picks the plain call on a CPU, so the cases call the packed
function directly and the model test tells the dispatch it is on a TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from fedml_tpu.models.resnet import ResNetCIFAR
from fedml_tpu.obs import perf_instrument
from fedml_tpu.obs.metrics import REGISTRY
from fedml_tpu.ops import packed_conv as pc

# Cin, Cout, W, the kernel gradient's P, the operand the rule lays out for it
SHAPES = [(3, 16, 32, 8, "dy"), (16, 16, 32, 8, "x"), (32, 32, 16, 4, "x"),
          (64, 64, 8, 2, "dy")]
TOL = 5e-6


def plain(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                    dimension_numbers=pc._NHWC,
                                    precision=lax.Precision.HIGHEST)


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def out_and_grads(fn, x, w, dy):
    y, vjp = jax.vjp(fn, x, w)
    return (y, *vjp(dy))


def sites_by(label: str) -> dict:
    """``fed_conv_sites_total`` summed by one label's values."""
    out = {}
    fam = REGISTRY.snapshot().get("fed_conv_sites_total") or {}
    for labels, v in fam.items():
        value = dict(kv.split("=") for kv in labels.split(","))[label]
        out[value] = out.get(value, 0) + v
    return out


def gained(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("lays_out", ["x", "dy"])
@pytest.mark.parametrize("forward", [True, False], ids=["all", "grad_only"])
@pytest.mark.parametrize("vmapped", [False, True], ids=["alone", "vmap10"])
@pytest.mark.parametrize("cin,cout,width,p,rule", SHAPES)
def test_packed_equals_plain(monkeypatch, cin, cout, width, p, rule, vmapped,
                             forward, lays_out):
    """``all``: forward, input gradient and kernel gradient packed by P;
    ``grad_only``: the kernel gradient alone, as the rule has it for P > 2.
    ``x``: the kernel gradient as that of ``conv(dy, K)`` at the cotangent
    ``x``, flipped and transposed; ``dy``: autodiff's of the packed call.
    Each shape runs both, the one the rule does not pick there too (the
    stem's ``x`` form is the only one whose transposes change a shape)."""
    shape = (3, 3, cin, cout)
    assert pc.pack_factor(shape, (1, 1), width, "tpu", grad=True) == p
    assert pc.grad_lays_out(
        shape, pc.pack_factor(shape, (1, 1), width, "tpu"), p) == rule
    monkeypatch.setattr(pc, "grad_lays_out", lambda shape, p, p_grad: lays_out)
    lead = (10,) if vmapped else ()
    k = jax.random.split(jax.random.PRNGKey(cin + width), 3)
    x = jax.random.normal(k[0], lead + (2, width, width, cin))
    w = jax.random.normal(k[1], lead + (3, 3, cin, cout)) * 0.1
    dy = jax.random.normal(k[2], lead + (2, width, width, cout))

    def packed(x, w):
        return pc.packed_conv3x3(x, w, p if forward else 1, p,
                                 lax.Precision.HIGHEST)

    def both(fn):
        f = lambda x, w, dy: out_and_grads(fn, x, w, dy)  # noqa: E731
        return jax.jit(jax.vmap(f) if vmapped else f)(x, w, dy)

    for name, got, want in zip(("y", "dx", "dw"), both(packed), both(plain)):
        assert got.shape == want.shape
        assert rel(got, want) < TOL, name


def test_packed_kernel_holds_every_tap_once():
    w = jnp.arange(3 * 3 * 2 * 5, dtype=jnp.float32).reshape(3, 3, 2, 5) + 1
    wp = pc.pack_kernel(w, 4)
    assert wp.shape == (3, 6, 2, 20)
    for q in range(4):
        block = wp[..., q * 5:(q + 1) * 5]
        np.testing.assert_array_equal(block[:, q:q + 3], w)
        assert float(jnp.abs(block).sum()) == float(jnp.abs(w).sum())


@pytest.mark.parametrize("kernel,strides,width,platform,want", [
    # (forward and input gradient, kernel gradient)
    ((3, 3, 16, 16), (1, 1), 32, "tpu", (1, 8)),
    ((3, 3, 32, 32), (1, 1), 16, "tpu", (1, 4)),
    ((3, 3, 64, 64), (1, 1), 8, "tpu", (2, 2)),
    ((3, 3, 3, 16), (1, 1), 32, "tpu", (1, 8)),
    ((3, 3, 16, 32), (2, 2), 32, "tpu", (1, 1)),    # stride 2
    ((1, 1, 16, 32), (1, 1), 32, "tpu", (1, 1)),    # 1x1 shortcut
    ((5, 5, 1, 32), (1, 1), 28, "tpu", (1, 1)),     # the CNN's 5x5
    ((3, 3, 16, 16), (1, 1), 31, "tpu", (1, 1)),    # W % P != 0 for all P > 1
    ((3, 3, 16, 16), (1, 1), 12, "tpu", (1, 4)),    # the largest P dividing W
    ((3, 3, 16, 16), (1, 1), 6, "tpu", (1, 2)),     # P = 2 fills 32 columns
    ((3, 3, 64, 128), (1, 1), 8, "tpu", (1, 1)),    # Cout >= 128
    ((3, 3, 64, 256), (1, 1), 8, "tpu", (1, 1)),
    ((3, 3, 16, 16), (1, 1), 32, "cpu", (1, 1)),    # not traced for a TPU
    ((3, 3, 64, 64), (1, 1), 8, "gpu", (1, 1)),
])
def test_pack_factor_is_the_rule(kernel, strides, width, platform, want):
    assert (pc.pack_factor(kernel, strides, width, platform),
            pc.pack_factor(kernel, strides, width, platform,
                           grad=True)) == want


@pytest.mark.parametrize("kernel,p,p_grad,want", [
    ((3, 3, 16, 16), 1, 8, "x"),
    ((3, 3, 32, 32), 1, 4, "x"),
    ((3, 3, 64, 64), 2, 2, "dy"),      # forward pass packed too
    ((3, 3, 64, 64), 1, 2, "x"),
    ((3, 3, 3, 16), 1, 8, "dy"),       # the stem: 24 columns from x
    ((3, 3, 16, 32), 1, 4, "dy"),
    ((3, 3, 16, 16), 1, 1, "none"),    # not packed: neither
    ((1, 1, 16, 32), 1, 1, "none"),
])
def test_grad_lays_out_is_the_rule(kernel, p, p_grad, want):
    assert pc.grad_lays_out(kernel, p, p_grad) == want


def test_swapped_kernel_gradient_reshapes_x_and_reads_dy_as_it_came(
        monkeypatch):
    """The operands of the backward pass's convolutions, at 16 to 32
    channels so that the shapes tell ``x`` from ``dy``: in the ``x`` form
    the kernel gradient's are ``dy`` as it came and ``x`` as
    ``[B, H, W/P, P*Cin]``, and no convolution sees a reshaped ``dy``."""
    x, dy = jnp.ones((2, 16, 16, 16)), jnp.ones((2, 16, 16, 32))
    w = jnp.ones((3, 3, 16, 32))

    def conv_operands(lays_out):
        monkeypatch.setattr(pc, "grad_lays_out",
                            lambda shape, p, p_grad: lays_out)
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "conv_general_dilated":
                    found.append({v.aval.shape for v in eqn.invars})
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jax.make_jaxpr(
            lambda dy: pc._bwd(1, 4, None, (x, w), dy))(dy).jaxpr)
        return found

    # (jax.vjp's own forward call is traced too, and dead)
    packed_dy, packed_x = (2, 16, 4, 128), (2, 16, 4, 64)
    assert {x.shape, packed_dy} in conv_operands("dy")
    swapped = conv_operands("x")
    assert {dy.shape, packed_x} in swapped
    assert not any(packed_dy in operands for operands in swapped)


def test_dispatch_hands_everything_else_on(monkeypatch):
    """Dilated, grouped, VALID-padded or NCHW calls reach
    ``lax.conv_general_dilated`` as they came, on a TPU too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jnp.ones((2, 8, 8, 16))
    w = jnp.ones((3, 3, 16, 16))
    before = perf_instrument.conv_sites()
    laid_out = sites_by("lays_out")
    for kw in ({"rhs_dilation": (2, 2)},
               {"lhs_dilation": (2, 2), "padding": ((1, 1), (1, 1))},
               {"padding": "VALID"},
               {"dimension_numbers": ("NHWC", "HWIO", "NHWC")}):
        kw = {"padding": "SAME", "dimension_numbers": pc._NHWC, **kw}
        pad = kw.pop("padding")
        got = pc.conv_general_dilated(x, w, (1, 1), pad, **kw)
        want = lax.conv_general_dilated(x, w, (1, 1), pad, **kw)
        np.testing.assert_array_equal(got, want)
    grouped = pc.conv_general_dilated(
        x, jnp.ones((3, 3, 8, 16)), (1, 1), "SAME",
        dimension_numbers=pc._NHWC, feature_group_count=2)
    assert grouped.shape == (2, 8, 8, 16)
    after = perf_instrument.conv_sites()
    assert after["plain"] - before["plain"] == 5
    assert after["packed"] == before["packed"]
    y = pc.conv_general_dilated(x, w, (1, 1), "SAME",
                                dimension_numbers=pc._NHWC)
    assert perf_instrument.conv_sites()["packed"] - after["packed"] == 1
    assert rel(y, plain(x, w)) < TOL
    for cin, cout in ((3, 16), (64, 64)):
        pc.conv_general_dilated(x[..., :1].repeat(cin, -1),
                                jnp.ones((3, 3, cin, cout)), (1, 1), "SAME",
                                dimension_numbers=pc._NHWC)
    assert gained(sites_by("lays_out"), laid_out) == {"none": 5, "x": 1,
                                                      "dy": 2}


def _tree(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 32, 32, 3)))
    return {jax.tree_util.keystr(k): v.shape
            for k, v in jax.tree_util.tree_leaves_with_path(shapes)}


def test_resnet56_group_norm_tree_is_the_parents(monkeypatch):
    """The tree the benchmark's own weights and every checkpoint fit: 57
    convolution kernels in their [kh, kw, Cin, Cout] shapes, 855,770
    parameters, the same on either path."""
    tree = _tree(ResNetCIFAR(depth=56, norm_type="group"))
    assert len(tree) == 173
    assert sum(int(np.prod(s)) for s in tree.values()) == 855_770
    assert tree["['params']['Conv_0']['kernel']"] == (3, 3, 3, 16)
    blocks = [f"_GNBasicBlock_{i}" for i in range(27)]
    for i, block in enumerate(blocks):
        cout = 16 << (i // 9)
        cin = cout // 2 if i in (9, 18) else cout
        base = f"['params']['{block}']"
        assert tree[f"{base}['Conv_0']['kernel']"] == (3, 3, cin, cout)
        assert tree[f"{base}['Conv_1']['kernel']"] == (3, 3, cout, cout)
        assert (f"{base}['Conv_2']['kernel']" in tree) == (i in (9, 18))
        if i in (9, 18):
            assert tree[f"{base}['Conv_2']['kernel']"] == (1, 1, cin, cout)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _tree(ResNetCIFAR(depth=56, norm_type="group")) == tree


@pytest.mark.parametrize("norm", ["group", "batch", "none"])
def test_resnet56_packed_matches_plain_and_counts_53_of_57(monkeypatch, norm):
    """Logits and gradient of ResNet-56 under each norm with the packed path
    taken (the dispatch told it is on a TPU) against the plain model's, from
    random weights so that every residual branch contributes; the 35 packed
    sites of stages 1 and 2 lay ``x`` out for the kernel gradient, stage 3's
    17 and the stem ``dy``."""
    model = ResNetCIFAR(depth=56, norm_type=norm)
    k = jax.random.split(jax.random.PRNGKey(56), 3)
    x = jax.random.normal(k[0], (4, 32, 32, 3))
    labels = jnp.arange(4) % 10
    variables = model.init(k[1], x)
    leaves, treedef = jax.tree.flatten(variables["params"])
    # zero-initialised leaves (norm biases) get values too
    params = treedef.unflatten([
        leaf + 0.05 * jax.random.normal(kk, leaf.shape)
        for leaf, kk in zip(leaves, jax.random.split(k[2], len(leaves)))])
    rest = {c: v for c, v in variables.items() if c != "params"}

    def loss(params):
        logits, _ = model.apply({"params": params, **rest}, x, train=True,
                                mutable=list(rest))
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, labels[:, None], 1).mean(), logits

    run = lambda: jax.jit(jax.value_and_grad(loss, has_aux=True))(params)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        (want_loss, want_logits), want_grad = run()
        before = perf_instrument.conv_sites()
        laid_out = sites_by("lays_out")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        (got_loss, got_logits), got_grad = run()
    after = perf_instrument.conv_sites()
    assert after["packed"] - before["packed"] == 53
    assert after["plain"] - before["plain"] == 4
    assert gained(sites_by("lays_out"), laid_out) == {"x": 35, "dy": 18,
                                                      "none": 4}
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    assert rel(got_logits, want_logits) < 1e-4
    worst = max(jax.tree.leaves(jax.tree.map(rel, got_grad, want_grad)))
    assert worst < 1e-3, worst
