"""What the plain references share: a convolution, and weights from a key."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# std of a unit normal truncated to [-2, 2]: LeCun-normal divides by it
_TRUNC_STD = 0.87962566103423978


def conv(x, kernel, stride: int = 1):
    """NHWC x HWIO convolution, SAME padding."""
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv_bf16(x, kernel, stride: int = 1):
    """``conv`` on bfloat16 casts of both operands, the result back in the
    type of ``x``: what a control lowers."""
    return conv(x.astype(jnp.bfloat16), kernel.astype(jnp.bfloat16),
                stride).astype(x.dtype)


def in_bf16(forward):
    """``forward`` computed in bfloat16 from float32 masters: weights and
    images cast on the way in, logits cast back. The control that every
    configuration stating float32 has to fail."""
    def lowered(params, x):
        cast = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
        return forward(jax.tree.map(cast, params), cast(x)).astype(
            jnp.float32)
    return lowered


def _leaf(name, shape, key):
    if name == "kernel":
        fan_in = math.prod(shape[:-1])
        std = math.sqrt(1.0 / fan_in) / _TRUNC_STD
        return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                 jnp.float32)
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    return jnp.zeros(shape, jnp.float32)


def init_tree(shapes: dict, key, zero=lambda names: False):
    """A float32 parameter tree of ``shapes`` in one jitted call: kernels
    LeCun-normal, norm scales one, biases zero; a leaf whose path of names
    ``zero`` accepts starts at zero."""
    flat, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda v: isinstance(v, tuple))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        return [jnp.zeros(shape, jnp.float32)
                if zero(tuple(p.key for p in path))
                else _leaf(path[-1].key, shape, k)
                for (path, shape), k in zip(flat, keys)]

    return jax.tree.unflatten(treedef, make(key))
