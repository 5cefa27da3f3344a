"""Plain reference of ``lfm2_24b_a2b_ep8``: LiquidAI's LFM2-24B-A2B
(``model_type`` ``lfm2_moe``) as one chip's share of an 8-way expert
deployment, in straightforward ``jax.numpy`` and float32 at matmul precision
``highest``. Imports nothing of the program.

``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``. Layer ``l`` on hidden ``h``
[T, D], in the order of the configuration's ``layer_types``:

    u = rms(h; operator_norm)
    conv:  [B | C | X] = u in_proj;  z_t = sum_j conv_kernel[j] * (B*X)_{t-L+1+j}
           (depthwise, causal, zeros before t = 0);  a = (C * z) out_proj
    full_attention:  q = u q_proj, k = u k_proj, v = u v_proj; q and k
           normed over each head's width (one gain vector each), then rope
           (rotate-half pairing); a = causal softmax(q k^T / sqrt(head))
           v o_proj, each key/value head serving H / KV query heads
    h = h + a;  m = rms(h; ffn_norm)
    l < num_dense_layers:  f = (silu(m w1) * (m w3)) w2
    else:  s = sigmoid(m router), in float32;  S = top-k of (s + expert_bias);
           w_e = s_e / (sum_{e in S} s_e + 1e-6) * routed_scaling_factor;
           f = sum over e in S AND held here of w_e (silu(m w1_e) * (m w3_e)) w2_e
    h = h + f

Embedding lookup in, ``rms(h; out_norm)`` and the embedding's transpose out.

Departures from the published model, each also under ``assumed`` in
``configs/lfm2_24b_a2b_ep8.json``:

- the head is tied to the embedding (the config has no key for tying);
- ``expert_bias`` selects and never weighs, and nothing trains it: it is a
  leaf behind ``stop_gradient`` (its update rule is not in the config);
- the experts a chip does not hold add nothing: ``w1``, ``w3`` and ``w2``
  hold experts ``experts_held[0] .. experts_held[1] - 1`` of the
  ``num_experts`` the router scores, and what the others would add is left
  out (the chip's share of an expert-parallel deployment, no exchange);
- every held expert is computed for every token and weighed by zero where
  the token did not choose it: plain, and 16 times the program's work.

The module binds the configuration file's sizes for the harness
(``init_params``, ``forward``); ``make(sizes)`` builds both for any sizes,
which is how the CPU tests run the model small.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import cells
from .common import _TRUNC_STD

EMBED_STD = 0.02
BIAS_STD = 0.01
_HIGHEST = jax.lax.Precision.HIGHEST


def layer_shapes(sz: dict, kind: str, dense: bool) -> dict:
    d, hd = sz["hidden_size"], sz["head_dim"]
    shapes = {"operator_norm": (d,), "ffn_norm": (d,)}
    if kind == "conv":
        shapes.update(in_proj=(d, 3 * d), conv_kernel=(sz["conv_L_cache"], d),
                      out_proj=(d, d))
    else:
        nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
        shapes.update(q_proj=(d, nq * hd), k_proj=(d, nkv * hd),
                      v_proj=(d, nkv * hd), o_proj=(nq * hd, d),
                      q_norm=(hd,), k_norm=(hd,))
    if dense:
        f = sz["intermediate_size"]
        shapes.update(w1=(d, f), w3=(d, f), w2=(f, d))
    else:
        f, held = sz["moe_intermediate_size"], sz["experts_held"]
        e = held[1] - held[0]
        shapes.update(router=(d, sz["num_experts"]),
                      expert_bias=(sz["num_experts"],),
                      experts_w1=(e, d, f), experts_w3=(e, d, f),
                      experts_w2=(e, f, d))
    return shapes


def shapes(sz: dict) -> dict:
    """The parameter tree's shapes; the program's model publishes the same
    names, so that one tree serves both sides."""
    tree = {"embedding": (sz["vocab_size"], sz["hidden_size"]),
            "out_norm": (sz["hidden_size"],)}
    for i, kind in enumerate(sz["layer_types"]):
        tree[f"layer_{i}"] = layer_shapes(sz, kind, i < sz["num_dense_layers"])
    return tree


def _leaf(name: str, shape, key):
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "embedding":
        return EMBED_STD * jax.random.normal(key, shape, jnp.float32)
    if name == "expert_bias":
        return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    # a matrix [.., fan_in, fan_out]; the short convolution's taps [L, D]
    # are a fan-in of L
    fan_in = shape[0] if name == "conv_kernel" else shape[-2]
    std = math.sqrt(1.0 / fan_in) / _TRUNC_STD
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rope(x, theta: float):
    """x [B, T, H, hd]; rotate-half pairing: lane i turns with lane
    i + hd / 2 by t / theta^(2i / hd)."""
    hd, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def short_conv(p, u, sz):
    b, c, x = jnp.split(jnp.dot(u, p["in_proj"], precision=_HIGHEST), 3, -1)
    bx = b * x
    taps = sz["conv_L_cache"]
    padded = jnp.pad(bx, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(p["conv_kernel"][j] * padded[:, j:j + bx.shape[1]]
            for j in range(taps))
    return jnp.dot(c * z, p["out_proj"], precision=_HIGHEST)


def attention(p, u, sz):
    bsz, t, _ = u.shape
    nq, nkv, hd = (sz["num_attention_heads"], sz["num_key_value_heads"],
                   sz["head_dim"])
    eps, theta = sz["norm_eps"], sz["rope_theta"]
    q = jnp.dot(u, p["q_proj"], precision=_HIGHEST).reshape(bsz, t, nq, hd)
    k = jnp.dot(u, p["k_proj"], precision=_HIGHEST).reshape(bsz, t, nkv, hd)
    v = jnp.dot(u, p["v_proj"], precision=_HIGHEST).reshape(bsz, t, nkv, hd)
    q = rope(rms(q, p["q_norm"], eps), theta)
    k = rope(rms(k, p["k_norm"], eps), theta)
    k = jnp.repeat(k, nq // nkv, axis=2)  # head h reads kv head h // (nq/nkv)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=_HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HIGHEST)
    return jnp.dot(out.reshape(bsz, t, nq * hd), p["o_proj"],
                   precision=_HIGHEST)


def gated_mlp(m, w1, w3, w2):
    return jnp.dot(jax.nn.silu(jnp.dot(m, w1, precision=_HIGHEST))
                   * jnp.dot(m, w3, precision=_HIGHEST), w2,
                   precision=_HIGHEST)


def route(p, m, sz, *, select_with_bias=True, normalise=True):
    """[.., num_experts] weights, zero outside the chosen top-k. The two
    flags plant the faults the limits are read against."""
    s = jax.nn.sigmoid(jnp.dot(m.astype(jnp.float32),
                               p["router"].astype(jnp.float32),
                               precision=_HIGHEST))
    bias = jax.lax.stop_gradient(p["expert_bias"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + bias if select_with_bias else s,
                              sz["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, sz["num_experts"],
                                    dtype=jnp.float32), axis=-2)
    w = s * picked
    if normalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w * sz["routed_scaling_factor"]


def expert_layer(p, m, sz, **faults):
    w = route(p, m, sz, **faults).astype(m.dtype)
    lo, hi = sz["experts_held"]

    def add_expert(f, held):  # a loop, not a kernel: one expert at a time
        w1, w3, w2, w_e = held
        return f + w_e[..., None] * gated_mlp(m, w1, w3, w2), None

    f, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (p["experts_w1"], p["experts_w3"], p["experts_w2"],
         jnp.moveaxis(w[..., lo:hi], -1, 0)))
    return f


def make(sizes: dict, **faults):
    """(init_params(key), forward(params, tokens [B, T]) -> logits) for
    ``sizes``: the keys of the configuration file's ``model.kwargs``."""
    sz = dict(sizes)
    sz.setdefault("head_dim", sz["hidden_size"] // sz["num_attention_heads"])
    eps = sz["norm_eps"]
    tree = shapes(sz)

    def init_params(key):
        flat, treedef = jax.tree.flatten_with_path(
            tree, is_leaf=lambda v: isinstance(v, tuple))

        @jax.jit
        def draw(key):
            keys = jax.random.split(key, len(flat))
            return [_leaf(path[-1].key, shape, k)
                    for (path, shape), k in zip(flat, keys)]

        return jax.tree.unflatten(treedef, draw(key))

    def forward(params, tokens):
        with jax.default_matmul_precision("highest"):
            h = jnp.take(params["embedding"], tokens, axis=0)
            for i, kind in enumerate(sz["layer_types"]):
                p = params[f"layer_{i}"]
                u = rms(h, p["operator_norm"], eps)
                h = h + (short_conv(p, u, sz) if kind == "conv"
                         else attention(p, u, sz))
                m = rms(h, p["ffn_norm"], eps)
                h = h + (gated_mlp(m, p["w1"], p["w3"], p["w2"])
                         if i < sz["num_dense_layers"]
                         else expert_layer(p, m, sz, **faults))
            h = rms(h, params["out_norm"], eps)
            return jnp.dot(h, params["embedding"].T, precision=_HIGHEST)

    return init_params, forward


def in_bf16(forward):
    """``forward`` computed in bfloat16 from float32 masters: the control
    that a configuration stating float32 has to fail."""
    def lowered(params, tokens):
        return forward(jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
                       tokens).astype(jnp.float32)
    return lowered


SIZES = cells._json("configs", "lfm2_24b_a2b_ep8.json")["model"]["kwargs"]
init_params, forward = make(SIZES)

# what the limits are read against (``tests/chip_readings_lm.py``): each put in
# the program's place has to come out not correct
CONTROLS = {
    "reference_bf16": in_bf16(forward),
    "fault_select_without_bias": make(SIZES, select_with_bias=False)[1],
    "fault_weights_not_normalised": make(SIZES, normalise=False)[1],
}
