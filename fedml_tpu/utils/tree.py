"""Pytree utilities — the tensor bookkeeping layer.

The reference does per-parameter dict loops on the host (e.g. the weighted sum
in FedAVGAggregator.aggregate, fedml_api/distributed/fedavg/FedAVGAggregator.py:58-87
and vectorize_weight in fedml_core/robustness/robust_aggregation.py:4-9). Here the
same operations are pure jax.tree transforms that stay on device and fuse under jit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    """a - b, leafwise. The FedOpt pseudo-gradient (w_old - w_avg)."""
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(t, s):
    return jax.tree.map(lambda x: x * s, t)


def tree_zeros_like(t):
    return jax.tree.map(jnp.zeros_like, t)


def tree_stack(trees):
    """Stack a list of pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def tree_unstack(tree, n):
    """Inverse of tree_stack: a stacked pytree -> list of n pytrees."""
    return [jax.tree.map(lambda x, i=i: x[i], tree) for i in range(n)]


@jax.named_scope("fed_aggregate")
def tree_weighted_mean(stacked, weights):
    """Weighted mean over the leading axis of a stacked pytree.

    ``stacked`` leaves have shape [K, ...]; ``weights`` has shape [K] and is
    normalized internally, so callers pass raw sample counts. This is the
    device-side equivalent of the server's per-key weighted averaging loop
    (reference FedAVGAggregator.py:72-80).
    """
    w = weights / jnp.maximum(jnp.sum(weights), 1e-12)
    return jax.tree.map(lambda x: jnp.tensordot(w, x, axes=([0], [0])), stacked)


def tree_vectorize(t):
    """Flatten a pytree into one 1-D vector (robust_aggregation.py:4-9 analogue)."""
    leaves = jax.tree.leaves(t)
    return jnp.concatenate([jnp.ravel(x) for x in leaves]) if leaves else jnp.zeros((0,))


def tree_unvectorize(vec, like):
    """Inverse of tree_vectorize given a template pytree ``like``."""
    leaves, treedef = jax.tree.flatten(like)
    out, i = [], 0
    for leaf in leaves:
        n = leaf.size
        out.append(jnp.reshape(vec[i : i + n], leaf.shape).astype(leaf.dtype))
        i += n
    return jax.tree.unflatten(treedef, out)


def tree_global_norm(t):
    """L2 norm over all leaves, computed without materializing the flat vector."""
    leaves = jax.tree.leaves(t)
    if not leaves:
        return jnp.zeros(())
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def tree_size(t) -> int:
    """Total number of scalars in a pytree (static)."""
    return sum(x.size for x in jax.tree.leaves(t))


def tree_cast(t, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), t)
