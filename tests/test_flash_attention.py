"""Pallas flash attention vs the dense reference (fwd + grads).

Runs in interpreter mode on the CPU test mesh; compiles with Mosaic on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import flash_attention
from fedml_tpu.parallel.ring_attention import full_attention


def _rand_qkv(key, B=2, T=96, H=2, D=32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (B, T, H, D)
    return (jax.random.normal(kq, shape, jnp.float32),
            jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal, 32, 32)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_handles_ragged_T():
    # T=70 not a multiple of the 32-block: internal padding must be exact
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), T=70)
    out = flash_attention(q, k, v, True, 32, 32)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), B=1, T=64, H=2, D=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 32, 32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_flash_under_jit_and_vmap():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), B=2, T=64, H=2, D=16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 32, 32))
    out = f(q, k, v)
    assert out.shape == q.shape and bool(jnp.all(jnp.isfinite(out)))


def test_transformer_lm_with_flash_kernel():
    from fedml_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=50, dim=32, depth=1, num_heads=2,
                          max_len=64, use_flash=True)
    ref = TransformerLM(vocab_size=50, dim=32, depth=1, num_heads=2, max_len=64)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 48), 0, 50)
    params = model.init(jax.random.PRNGKey(1), tokens)
    out_f = model.apply(params, tokens)
    out_r = ref.apply(params, tokens)  # same params: flash vs dense path
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_flash_matches_dense():
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.parallel.ring_attention import (full_attention,
                                                   ring_attention_flash_sharded)

    mesh = jax.make_mesh((8,), ("seq",))
    B, T, H, D = 1, 128, 2, 16
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)

    for causal in (False, True):
        f = ring_attention_flash_sharded(mesh, "seq", causal=causal,
                                         block_q=16, block_k=16)
        out = f(q, k, v)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


def test_ring_attention_flash_gradients():
    from fedml_tpu.parallel.ring_attention import (full_attention,
                                                   ring_attention_flash_sharded)

    mesh = jax.make_mesh((4,), ("seq",))
    B, T, H, D = 1, 64, 2, 8
    key = jax.random.PRNGKey(6)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)

    ring = ring_attention_flash_sharded(mesh, "seq", causal=True,
                                        block_q=16, block_k=16)
    with jax.set_mesh(mesh):
        g_ring = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                          argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_ulysses_flash_matches_dense():
    from fedml_tpu.parallel.ring_attention import (full_attention,
                                                   ulysses_attention_sharded)

    mesh = jax.make_mesh((2,), ("seq",))
    B, T, H, D = 1, 64, 4, 16
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    f = ulysses_attention_sharded(mesh, "seq", causal=True, use_flash=True)
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(full_attention(q, k, v, causal=True)),
                               rtol=3e-5, atol=3e-5)


def test_flash_gradients_under_strict_vma_shard_map():
    """flash inside shard_map(check_vma=True): the op must be vma-clean —
    off-TPU it dispatches to its jnp twin (Pallas interpret lowering is a
    while_loop of vma-less dynamic_slices and would be rejected), on TPU
    the Mosaic kernels carry vma-typed out_shapes."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((2,), ("seq",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), B=1, T=64, H=2, D=16)

    def local_grads(q, k, v):
        # per-shard: full attention over this device's T-slice
        return jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, True, 16, 16) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    f = jax.jit(jax.shard_map(
        local_grads, mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        check_vma=True))
    gs = f(q, k, v)

    # oracle: the same sliced computation unsharded
    def ref_grads(q, k, v):
        half = q.shape[1] // 2
        tot = 0.0
        for s in (slice(0, half), slice(half, None)):
            tot = tot + jnp.sum(
                full_attention(q[:, s], k[:, s], v[:, s], causal=True) ** 2)
        return tot

    gr = jax.grad(ref_grads, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_forced_pallas_mode_off_tpu_raises_instead_of_substituting(monkeypatch):
    """``_mode`` is the one decision of what serves a call. Forced to the
    Mosaic kernels where they cannot lower, the op must raise — never hand
    back the dense twin's answer (the fallback that once let a TPU run
    'work' without running the kernel)."""
    import importlib

    fa = importlib.import_module("fedml_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_mode", lambda x: "pallas")
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), B=1, T=64, H=2, D=16)
    with pytest.raises(Exception, match="interpret mode"):
        fa.flash_attention(q, k, v, True, 32, 32)
    with pytest.raises(Exception, match="interpret mode"):
        jax.grad(lambda q: jnp.sum(fa.flash_attention(q, k, v, True, 32, 32)))(q)
