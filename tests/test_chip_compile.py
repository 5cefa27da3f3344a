"""Compile the main path's programs for a described TPU v5e, without a chip.

The chip's compiler is installed next to the CPU backend and compiles for a
topology that is described, not attached (the on-chip-measurement guide,
section 2): what it refuses here it refuses on the chip, at no chip time.
Nothing runs, so these say nothing about results or speed.

Code that asks ``jax.default_backend()`` sees the CPU here, so the flash
kernel's ``_mode`` is steered to 'pallas' by the test. The topology is
described inside a fixture (never at import: only one process may load the
TPU library), and the persistent compile cache is off around the compiles —
an entry written for a described chip cannot be read back without one.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("fedml_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on_chip(tree, one_chip):
    """Shapes of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                       sharding=one_chip), tree)


# the three shapes ops/flash_attention.py promises the v5e compiler accepts
FLASH_SHAPES = [((2, 1024, 8, 64), jnp.float32),
                ((2, 2048, 8, 64), jnp.bfloat16),
                ((1, 8192, 8, 128), jnp.bfloat16)]


@pytest.mark.parametrize("direction,kernels", [("fwd", 1), ("bwd", 3)])
@pytest.mark.parametrize(
    "shape,dtype", FLASH_SHAPES,
    ids=[f"T{s[1]}_D{s[3]}_{jnp.dtype(d).name}" for s, d in FLASH_SHAPES])
def test_flash_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                       monkeypatch, shape, dtype, direction,
                                       kernels):
    monkeypatch.setattr(fa, "_mode", lambda x: "pallas")
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    # forward kernel; backward = recomputed forward + dQ + dK/dV
    assert text.count("tpu_custom_call") == kernels


def test_flash_kernel_compiles_under_vmap_for_v5e(one_chip,
                                                  no_persistent_cache,
                                                  monkeypatch):
    """The round engine vmaps the local fit over clients, so the kernels are
    lowered with a leading client axis (chip_smoke's flash_lm shape)."""
    monkeypatch.setattr(fa, "_mode", lambda x: "pallas")
    x = jax.ShapeDtypeStruct((4, 2, 1024, 8, 64), jnp.float32,
                             sharding=one_chip)
    grad = jax.grad(
        lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, True) ** 2),
        argnums=(0, 1, 2))
    text = jax.jit(jax.vmap(grad)).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 3


def test_flash_above_vmem_limit_raises_before_lowering(monkeypatch):
    monkeypatch.setattr(fa, "_mode", lambda x: "pallas")
    x = jax.ShapeDtypeStruct((1, 32768, 8, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="shard the sequence"):
        jax.eval_shape(lambda q, k, v: fa.flash_attention(q, k, v, True),
                       x, x, x)


def test_femnist_cnn_round_step_compiles_for_v5e(one_chip,
                                                 no_persistent_cache):
    """The flagship per-round program at full width: CNNOriginalFedAvg,
    62 classes, 10 clients/round, bs 20, uint8 pixels."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI, FedAvgConfig
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.data.registry import load_dataset
    from fedml_tpu.models.cnn import CNNOriginalFedAvg

    data = load_dataset("femnist", client_num=20, uint8_pixels=True)
    cfg = FedAvgConfig(comm_round=1, client_num_in_total=20,
                       client_num_per_round=10, batch_size=20, lr=0.1,
                       max_batches=28)
    api = FedAvgAPI(data, classification_task(
        CNNOriginalFedAvg(only_digits=False)), cfg, donate=True)
    args = (jax.random.PRNGKey(0), api.net, api.server_opt_state,
            api._warmup_batch(api.num_batches), jnp.int32(0),
            jnp.zeros((10,), jnp.int32))
    compiled = api.round_fn.lower(*_on_chip(args, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


@pytest.mark.parametrize("path", ["plain", "packed"])
def test_resnet56_local_fit_compiles_for_v5e(one_chip, no_persistent_cache,
                                             monkeypatch, path):
    """One silo's local fit of the cross-silo cell: ResNet-56, group norm,
    CIFAR-10 shapes, 8 batches of 64 (benchmark/'s cifar_resnet56).
    ``ops/packed_conv.py`` asks ``jax.default_backend()`` which path serves
    the 3x3 convolutions; told it is a TPU, the width-packed one is what the
    chip's compiler gets, as on the chip."""
    import optax

    from fedml_tpu.core.local import LocalSpec, make_local_update
    from fedml_tpu.core.tasks import classification_task
    from fedml_tpu.models.resnet import ResNetCIFAR

    if path == "packed":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    task = classification_task(ResNetCIFAR(depth=56, num_classes=10,
                                           norm_type="group"))
    x = jnp.zeros((8, 64, 32, 32, 3), jnp.uint8)
    net = jax.eval_shape(task.init, jax.random.PRNGKey(0), x[0])
    fit = make_local_update(task, LocalSpec(optimizer=optax.sgd(0.01)))
    args = (jax.random.PRNGKey(0), net, x, jnp.zeros((8, 64), jnp.int32),
            jnp.ones((8, 64), jnp.float32))
    compiled = jax.jit(fit).lower(*_on_chip(args, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    assert ("fed_conv_packed" in compiled.as_text()) == (path == "packed")


def test_lfm2_moe_folded_round_compiles_for_v5e(one_chip,
                                                no_persistent_cache):
    """One round of the cross-silo language-model cell's fold
    (benchmark/'s lfm2_24b_a2b_ep8: 4 silos one after another, 2 batches of
    8 x 2048 tokens, float32 at ``highest``) at the cut's widths, from
    ``jax.eval_shape`` weights. One layer of each kind is kept (the dense
    short convolution, then attention with its expert layer): the whole cut
    of five layers compiles in 70 to 107 s on this host and was compiled by
    hand. With the blocks keeping their input alone it reads 1.88 GB of
    arguments + 10.72 GB of temporaries = 12.60 GB (CHANGES.md PR 33; the
    chip's allocator then read 11.08 GB); with the rule of
    ``lfm2_moe.kept_names`` on at ``KEPT_BYTES`` (every product's output
    kept, 5.17 GB by the shapes) 1.88 + 16.40 = 18.29 GB, where the chip's
    own compile fits the step in a peak of 15.28 GB (CHANGES.md PR 34: this
    analysis counts the kept outputs about twice). The fold's four copies
    of the weights and a step's temporaries fit the chip, and the scopes
    that ``chip_scopes.py`` reads reach the compiled program."""
    import json
    import os
    import types

    import optax

    from fedml_tpu.algorithms.fedavg import _gather_rows, _make_client_keys
    from fedml_tpu.core import client_fold
    from fedml_tpu.core.local import LocalSpec, make_local_update
    from fedml_tpu.core.tasks import routed_sequence_task
    from fedml_tpu.models.lfm2_moe import Lfm2MoeLM

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2_24b_a2b_ep8.json")) as f:
        sizes = json.load(f)["model"]["kwargs"]
    sizes = dict(sizes, layer_types=["conv", "full_attention"])
    task = routed_sequence_task(Lfm2MoeLM(**sizes))
    engine = types.SimpleNamespace(
        local_update=make_local_update(
            task, LocalSpec(optimizer=optax.sgd(0.001))),
        client_result_hook=None, _agg_weights=lambda n: n,
        _update_from_aggregate=lambda net, avg, opt, key: (avg, opt))
    make_step = client_fold.make_step(engine, _make_client_keys(17),
                                      _gather_rows)

    def one_round(net, dev_x, dev_y, idx, mask, nsamp, ids, keys):
        carry, ms = make_step(dev_x, dev_y)(
            (net, ()), (idx, mask, nsamp, ids, jnp.int32(0), keys, keys))
        return carry[0], ms

    silos, batches, bs, t = 4, 2, 8, 2048
    net = jax.eval_shape(task.init, jax.random.PRNGKey(0),
                         jnp.zeros((bs, t), jnp.int32))
    args = (net, jnp.zeros((256, t), jnp.int32), jnp.zeros((256, t), jnp.int32),
            jnp.zeros((silos, batches, bs), jnp.int32),
            jnp.ones((silos, batches, bs), jnp.float32),
            jnp.ones((silos,), jnp.float32), jnp.zeros((silos,), jnp.int32),
            jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(one_round, donate_argnums=(0,)).lower(
            *_on_chip(args, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 << 30
    text = compiled.as_text()
    for scope in ("fed_client_fold", "fed_moe_route", "fed_moe_experts",
                  "fed_short_conv", "fed_attention", "fed_gather"):
        assert scope in text, scope
    assert "tpu_custom_call" not in text  # XLA ops only


@pytest.mark.parametrize("lays_out,fused", [("x", True), ("dy", False)])
def test_norm_backward_fuses_into_packed_gradient_convs_for_v5e(
        one_chip, no_persistent_cache, monkeypatch, lays_out, fused):
    """Two group-norm blocks at 16 channels with their gradients, ``vmap``
    over 10 silos of 64 images at ``highest``, on the TPU path. With the
    saved input laid out for the packed kernel gradient (the rule's ``x``)
    no top-level loop fusion named ``GroupNorm_k/add_any`` writes a whole
    activation: the norm's backward sits in the gradient convolutions.
    With ``dy`` laid out instead (forced here; the form before PR 30) each
    of the four sites has one, which is what this test looks for."""
    import flax.linen as nn

    from fedml_tpu.models.resnet import _GNBasicBlock
    from fedml_tpu.ops import packed_conv as pc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pc.grad_lays_out((3, 3, 16, 16), 1, 8) == "x"
    monkeypatch.setattr(pc, "grad_lays_out", lambda shape, p, p_grad: lays_out)

    class TwoBlocks(nn.Module):
        @nn.compact
        def __call__(self, x):
            return _GNBasicBlock(16)(_GNBasicBlock(16)(x))

    model = TwoBlocks()
    x = jnp.zeros((64, 32, 32, 16))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)

    def loss(params, x):
        return (model.apply(params, x) ** 2).sum()

    silos = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((10,) + a.shape, a.dtype,
                                       sharding=one_chip), (params, x))
    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.vmap(jax.value_and_grad(loss, (0, 1)))).lower(
            *silos).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    assert entry.count("fed_conv_packed/transpose(jvp(jit(_packed)))"
                       "/conv_general_dilated") >= 4
    unfused = [line for line in entry.splitlines()
               if "kind=kLoop" in line
               and re.search(r"= f32\[10,64,32,32,16\]", line)
               and re.search(r'op_name="[^"]*/GroupNorm_\d+/add_any"', line)]
    assert (len(unfused) == 0) if fused else (len(unfused) == 4), unfused
