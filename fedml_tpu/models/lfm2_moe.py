"""LFM2-MoE language model (LiquidAI ``lfm2_moe``): gated short
convolutions, grouped-query attention with rotary positions and a norm on
each head's queries and keys, gated MLPs, and sigmoid-routed experts of
which this device may hold a share.

Layer ``l`` on hidden ``h`` (``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``):

    u = rms(h; operator_norm)
    conv:  [B | C | X] = u in_proj;  z_t = sum_j conv_kernel[j] (B*X)_{t-L+1+j};
           a = (C * z) out_proj
    full_attention:  causal softmax(rope(rms(q)) rope(rms(k))^T / sqrt(hd)) v
           o_proj, ``num_key_value_heads`` heads each serving a group of
           query heads
    h = h + a;  m = rms(h; ffn_norm)
    l < num_dense_layers:  f = (silu(m w1) * (m w3)) w2
    else (the expert layer):  s = sigmoid(m router), float32;
           S = top-k of (s + expert_bias);  w_e = s_e / (sum_S s + 1e-6) * scale;
           f = sum over e in S and held here of w_e expert_e(m)
    h = h + f

The head is the embedding's transpose. ``expert_bias`` selects and never
weighs; it is a parameter behind ``stop_gradient`` (no gradient trains it).

**The expert layer is told which experts it holds** (``experts_held``, a
range of the ``num_experts`` the router scores): it routes over all of them,
keeps the assignments that fall to its own and computes their part of the
sum; what the absent experts would add is left out, as one device of an
expert-parallel deployment leaves it to the others (there is no exchange
here). No token is dropped. Work follows the assignments held: they are
sorted by expert into tiles of ``moe_tile_rows`` rows, each tile of one
expert, inside a static budget of ``moe_row_budget`` rows a token (the
expectation is ``k * held / num_experts``; the worst case ``k``), and a loop
over the tiles multiplies each by its own expert's matrices. A step whose
tiles do not fit the budget takes, behind a ``lax.cond``, the exact path of
full size: every held expert for every token, weighed by zero where the
token chose another. (Under ``vmap`` a ``cond`` is a ``select`` and both
paths run: fold the clients, ``FedAvgConfig.client_fold="scan"``.) XLA ops
only; the tile loop, the dispatch and the combine carry their own backward
passes so that all four row movements are gathers and the experts'
gradients accumulate in place.

Each block is rematerialised (``nn.remat``) and keeps, beside its input, the
outputs of its matrix products as far as a byte budget allows
(``kept_names``, ``KEPT_BYTES``): the backward pass reads them and does not
run those products again. Norms, gates, the short convolution's taps, rotary
angles, softmax and the routing plan are made again; they cost no product.
At the benchmark cell's step (16,384 tokens, float32) a kept output weighs
134 MB at width 2048, 403 MB at 6144, 772 MB at 11776, an expert layer's
rows (12,288) 101 MB at width 2048 and 75.5 MB at 1536; a step too large
for any of them keeps one hidden state a layer, as before the rule.
Attention takes its queries in blocks of ``attention_query_block``
against the keys up to the block's end, so that a step's scores never stand
whole. ``moe_stats`` (collection, sown once a call where it is mutable) holds
the held experts' token counts by layer, the rows that held an assignment,
the rows computed and the layer-steps that took the full path.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu.obs import perf_instrument

STATS = "moe_stats"


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: tuple
    conv_L_cache: int
    norm_eps: float
    rope_theta: float
    routed_scaling_factor: float
    moe_row_budget: float
    moe_tile_rows: int
    attention_query_block: int


def rms(x, gain, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta: float):
    """x [B, T, H, hd], rotate-half pairing, angles in float32."""
    hd, t = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-x2, x1], -1).astype(jnp.float32)
    return (xf * cos + rot * sin).astype(x.dtype)


# ------------------------------------------------------------ short conv
@jax.named_scope("fed_short_conv")
def short_conv(u, in_proj, conv_kernel, out_proj):
    b, c, x = jnp.split(checkpoint_name(u @ in_proj, "conv_in"), 3, axis=-1)
    bx = b * x
    taps, t = conv_kernel.shape[0], bx.shape[1]
    padded = jnp.pad(bx, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(conv_kernel[j] * padded[:, j:j + t] for j in range(taps))
    return checkpoint_name((c * z) @ out_proj, "conv_out")


# ------------------------------------------------------------- attention
@functools.partial(jax.checkpoint, static_argnums=(3,))
def _attend_block(q, k, v, first):
    """q [B, qb, G, R, hd] at positions first.., k and v [B, first + qb, G,
    hd]: the block's scores stand alone and are made again in the backward
    pass."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(hd)
    qpos = first + jnp.arange(q.shape[1])
    seen = jnp.arange(k.shape[1])[None, :] <= qpos[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


@jax.named_scope("fed_attention")
def attention(u, p, sz: Sizes):
    bsz, t, _ = u.shape
    nq, nkv, hd = sz.num_attention_heads, sz.num_key_value_heads, sz.head_dim
    q = checkpoint_name(u @ p["q_proj"], "attn_q").reshape(bsz, t, nq, hd)
    k = checkpoint_name(u @ p["k_proj"], "attn_k").reshape(bsz, t, nkv, hd)
    v = checkpoint_name(u @ p["v_proj"], "attn_v").reshape(bsz, t, nkv, hd)
    q = rope(rms(q, p["q_norm"], sz.norm_eps), sz.rope_theta)
    k = rope(rms(k, p["k_norm"], sz.norm_eps), sz.rope_theta)
    q = q.reshape(bsz, t, nkv, nq // nkv, hd)  # head h reads kv head h // R
    qb = min(sz.attention_query_block, t)
    out = checkpoint_name(jnp.concatenate(
        [_attend_block(q[:, i:i + qb], k[:, :i + qb], v[:, :i + qb], i)
         for i in range(0, t, qb)], axis=1), "attn_context")
    return checkpoint_name(out.reshape(bsz, t, nq * hd) @ p["o_proj"],
                           "attn_out")


# ----------------------------------------------------------- expert layer
def gated_mlp(m, w1, w3, w2):
    return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


@jax.named_scope("fed_moe_route")
def route(m, router, expert_bias, k: int, scale: float):
    """(chosen [N, k] expert ids, weights [N, k]) in float32: the bias
    selects, the scores weigh."""
    s = jax.nn.sigmoid(jnp.dot(m.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    bias = lax.stop_gradient(expert_bias.astype(jnp.float32))
    _, chosen = lax.top_k(s + bias, k)
    # kept with the tiles' outputs, whose rows lie where THIS choice put
    # them: a backward pass that chose again from hidden states made again
    # can break a near tie the other way, and every later row of that
    # expert's group would then meet another token's kept output
    chosen = checkpoint_name(chosen, "moe_chosen")
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6) * scale
    return chosen, w


def plan_rows(chosen, lo: int, hi: int, rows: int, tile: int):
    """Where each held assignment's row lies when the assignments are
    sorted by expert and every expert's group is padded to whole tiles.
    ``chosen`` [N, k]. Returns integers alone:

      dest [N, k]      the assignment's row, ``rows`` where it is not held
                       here (or lies past the budget)
      row_src [rows]   the flat assignment a row holds, N * k for padding
      tile_expert      the held expert of each tile of ``tile`` rows
      counts [held]    assignments of each held expert
      padded_rows      rows the padded groups take in all
    """
    n_held = hi - lo
    flat = chosen.reshape(-1)
    held = (flat >= lo) & (flat < hi)
    local = jnp.where(held, flat - lo, n_held)
    onehot = local[:, None] == jnp.arange(n_held)[None, :]
    ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    rank = jnp.sum(jnp.where(onehot, ranks, 0), axis=1)
    counts = jnp.sum(onehot.astype(jnp.int32), axis=0)
    padded = (counts + tile - 1) // tile * tile
    ends = jnp.cumsum(padded)
    offsets, starts = ends - padded, jnp.cumsum(counts) - counts
    dest = jnp.where(held, offsets[jnp.minimum(local, n_held - 1)] + rank,
                     rows)
    dest = jnp.minimum(dest, rows).reshape(chosen.shape)
    # the inverse map by a stable sort: no scatter anywhere
    order = jnp.argsort(local, stable=True)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(rows // tile) * tile, side="right"),
        n_held - 1).astype(jnp.int32)
    row_expert = jnp.repeat(tile_expert, tile)
    within = jnp.arange(rows) - offsets[row_expert]
    valid = within < counts[row_expert]
    src = order[jnp.clip(starts[row_expert] + within, 0, flat.shape[0] - 1)]
    row_src = jnp.where(valid, src, flat.shape[0]).astype(jnp.int32)
    return dest, row_src, tile_expert, counts, ends[-1]


def _take_rows(padded, index):
    return jnp.take(padded, index, axis=0, mode="clip")


@jax.custom_vjp
def dispatch(m, dest, row_src):
    """x_rows [rows, D]: the token of each row, zero for padding."""
    k = dest.shape[1]
    token = jnp.minimum(row_src, dest.size - 1) // k
    return jnp.where((row_src < dest.size)[:, None],
                     jnp.take(m, token, axis=0), 0).astype(m.dtype)


def _dispatch_fwd(m, dest, row_src):
    return dispatch(m, dest, row_src), (dest,)


def _dispatch_bwd(res, dx):
    (dest,) = res
    padded = jnp.concatenate([dx, jnp.zeros_like(dx[:1])])
    dm = sum(_take_rows(padded, dest[:, j]) for j in range(dest.shape[1]))
    return dm, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, w, dest, row_src):
    """f [N, D] = sum_j w[n, j] * y[dest[n, j]], nothing where the
    assignment is not held."""
    padded = jnp.concatenate([y, jnp.zeros_like(y[:1])])
    return sum(w[:, j, None].astype(y.dtype) * _take_rows(padded, dest[:, j])
               for j in range(dest.shape[1]))


def _combine_fwd(y, w, dest, row_src):
    return combine(y, w, dest, row_src), (y, w, dest, row_src)


def _combine_bwd(res, df):
    y, w, dest, row_src = res
    k = dest.shape[1]
    valid = row_src < dest.size
    src = jnp.minimum(row_src, dest.size - 1)
    w_row = jnp.where(valid, jnp.take(w.reshape(-1), src), 0)
    dy = w_row[:, None].astype(df.dtype) * jnp.take(df, src // k, axis=0)
    padded = jnp.concatenate([y, jnp.zeros_like(y[:1])])
    dw = jnp.stack([jnp.sum(_take_rows(padded, dest[:, j]).astype(jnp.float32)
                            * df.astype(jnp.float32), axis=-1)
                    for j in range(k)], axis=1)
    return dy, dw.astype(w.dtype), None, None


combine.defvjp(_combine_fwd, _combine_bwd)


def _pick(w, e):
    return lax.dynamic_index_in_dim(w, e, 0, keepdims=False)


def _add_at(acc, e, g):
    return lax.dynamic_update_index_in_dim(acc, _pick(acc, e) + g, e, 0)


@jax.custom_vjp
def tiled_experts(x, w1, w3, w2, tile_expert):
    """y [rows, D]: each tile of ``rows / len(tile_expert)`` rows through
    the gated MLP of its own expert."""
    return _tiled_fwd(x, w1, w3, w2, tile_expert)[0]


def _tiled_fwd(x, w1, w3, w2, tile_expert):
    tiles = x.reshape(tile_expert.shape[0], -1, x.shape[-1])

    def one(_, inp):
        xt, e = inp
        h1, h3 = xt @ _pick(w1, e), xt @ _pick(w3, e)
        return None, ((jax.nn.silu(h1) * h3) @ _pick(w2, e), h1, h3)

    _, (y, h1, h3) = lax.scan(one, None, (tiles, tile_expert))
    tiles = checkpoint_name(tiles, "moe_tiles")
    h1, h3 = checkpoint_name(h1, "moe_h1"), checkpoint_name(h3, "moe_h3")
    y = checkpoint_name(y.reshape(x.shape), "moe_y")
    return y, (tiles, h1, h3, w1, w3, w2, tile_expert)


def _tiled_bwd(res, dy):
    tiles, h1, h3, w1, w3, w2, tile_expert = res

    def one(acc, inp):
        dw1, dw3, dw2 = acc
        xt, h1t, h3t, dyt, e = inp
        sig = jax.nn.sigmoid(h1t)
        act = h1t * sig
        dw2 = _add_at(dw2, e, (act * h3t).T @ dyt)
        dg = dyt @ _pick(w2, e).T
        dh1 = dg * h3t * (sig * (1 + h1t * (1 - sig)))
        dh3 = dg * act
        dw1 = _add_at(dw1, e, xt.T @ dh1)
        dw3 = _add_at(dw3, e, xt.T @ dh3)
        dx = dh1 @ _pick(w1, e).T + dh3 @ _pick(w3, e).T
        return (dw1, dw3, dw2), dx

    zeros = tuple(jnp.zeros_like(w) for w in (w1, w3, w2))
    (dw1, dw3, dw2), dx = lax.scan(
        one, zeros, (tiles, h1, h3, dy.reshape(tiles.shape), tile_expert))
    return dx.reshape(dy.shape), dw1, dw3, dw2, None


tiled_experts.defvjp(_tiled_fwd, _tiled_bwd)


def _budgeted(m, chosen, w, w1, w3, w2, plan):
    dest, row_src, tile_expert = plan
    y = tiled_experts(dispatch(m, dest, row_src), w1, w3, w2, tile_expert)
    return combine(y, w, dest, row_src)


def _full_size(lo: int, m, chosen, w, w1, w3, w2, plan):
    """Every held expert for every token, weighed by zero where the token
    chose another: exact for any routing, at ``held`` rows a token."""
    mine = (chosen - lo)[:, :, None] == jnp.arange(w1.shape[0])[None, None, :]
    by_expert = jnp.sum(jnp.where(mine, w[:, :, None], 0), axis=1)

    @jax.checkpoint
    def one(f, inp):
        w1e, w3e, w2e, col = inp
        return f + col[:, None].astype(m.dtype) * gated_mlp(m, w1e, w3e,
                                                             w2e), None

    f, _ = lax.scan(one, jnp.zeros_like(m), (w1, w3, w2, by_expert.T))
    return f


def _budget_rows(sz: Sizes, n: int) -> int:
    tile = sz.moe_tile_rows
    return max(math.ceil(sz.moe_row_budget * n / tile), 1) * tile


def expert_layer(m, p, sz: Sizes):
    """(f [N, D], stats) for m [N, D]: the held experts' part of the
    routed sum."""
    lo, hi = sz.experts_held
    n, tile = m.shape[0], sz.moe_tile_rows
    rows = _budget_rows(sz, n)
    chosen, w = route(m, p["router"], p["expert_bias"],
                      sz.num_experts_per_tok, sz.routed_scaling_factor)
    with jax.named_scope("fed_moe_experts"):
        dest, row_src, tile_expert, counts, padded = plan_rows(
            chosen, lo, hi, rows, tile)
        fits = padded <= rows
        f = lax.cond(fits, _budgeted, functools.partial(_full_size, lo),
                     m, chosen, w, p["experts_w1"], p["experts_w3"],
                     p["experts_w2"], (dest, row_src, tile_expert))
    counts = counts.astype(jnp.float32)
    stats = {"expert_tokens": counts, "rows_real": jnp.sum(counts),
             "rows_dispatched": jnp.where(fits, float(rows),
                                          float((hi - lo) * n)),
             "fallback_steps": 1.0 - fits.astype(jnp.float32)}
    return f, lax.stop_gradient(stats)


# ------------------------------------------- what a block keeps of its pass
# The bytes of named outputs that one step's blocks may keep for their
# backward passes, set from chip readings (one v5e, PERF.md section 6, PR
# 34) so that the benchmark cell's peak stays at least 1 GiB under the
# allocator's limit (``bytes_limit`` 16,909,336,064): at 16,384 tokens a step
# the rule then keeps 5,168,431,104 bytes by the shapes, every named output
# but the expert layers' dispatched rows, and the allocator's peak reads
# 15,283,312,128 (1.51 GiB under; 11,082,651,136 with nothing kept). The
# model cannot ask the device; an engine that can has only to hand its own
# figure to ``kept_names``. Under ``vmap`` over K clients the shapes seen
# here are one client's and K times the bytes are kept: fold the clients.
KEPT_BYTES = 5_200_000_000


def block_outputs(sz: Sizes, kind: str, dense: bool, batch: int,
                  seq_len: int, itemsize: int):
    """What a block's matrix products leave that its backward pass reads,
    as [(names, bytes, operations)]: the bytes the named outputs of one step
    weigh, and the forward operations that are not run again where they are
    kept. Each entry stands alone (keeping one saves its own product
    whatever else is kept), which is why the expert tiles' three products
    are one entry: one loop makes them, and it runs again whole if any of
    the three is missing. The experts chosen (int32) are kept with them:
    they say which token a kept row belongs to."""
    n, d = batch * seq_len, sz.hidden_size
    nq, nkv, hd = sz.num_attention_heads, sz.num_key_value_heads, sz.head_dim

    def product(name, inner, width):
        return (name,), n * width * itemsize, 2 * n * inner * width

    if kind == "conv":
        out = [product("conv_in", d, 3 * d), product("conv_out", d, d)]
    else:
        qb = min(sz.attention_query_block, seq_len)
        # query-key pairs of a sequence: each block against the keys up to
        # its end; scores and values are two products over them
        pairs = sum((min(i + qb, seq_len) - i) * min(i + qb, seq_len)
                    for i in range(0, seq_len, qb))
        out = [product("attn_q", d, nq * hd), product("attn_k", d, nkv * hd),
               product("attn_v", d, nkv * hd),
               (("attn_context",), n * nq * hd * itemsize,
                4 * batch * pairs * nq * hd),
               product("attn_out", nq * hd, d)]
    if dense:
        f = sz.intermediate_size
        return out + [product("mlp_h1", d, f), product("mlp_h3", d, f)]
    f, rows = sz.moe_intermediate_size, _budget_rows(sz, n)
    return out + [
        (("moe_chosen", "moe_h1", "moe_h3", "moe_y"),
         rows * (2 * f + d) * itemsize + n * sz.num_experts_per_tok * 4,
         6 * rows * d * f),
        (("moe_tiles",), rows * d * itemsize, 0)]  # a gather, no product


def kept_names(sz: Sizes, layer_types, num_dense_layers: int, batch: int,
               seq_len: int, itemsize: int, budget: float):
    """Which named outputs each block keeps for its backward pass, from the
    step's static shapes alone: ({name: kept} by layer, bytes kept).

    The entries of ``block_outputs`` over all layers are ranked by the
    operations saved a byte kept (twice the product's inner width over the
    item size, so ties are common: the lighter first, then the earlier
    layer) and taken in that order while their bytes stay inside
    ``budget``; the first that does not fit ends it. At a given sequence
    length the order is the same for every batch and every entry's bytes
    grow with the batch, so a larger batch keeps a subset of what a smaller
    one keeps, and in the end nothing: the block then keeps its input alone.
    (Longer sequences move one entry up, attention's context, whose
    products grow with the keys seen.)"""
    ranked = []
    for layer, kind in enumerate(layer_types):
        for names, nbytes, ops in block_outputs(
                sz, kind, layer < num_dense_layers, batch, seq_len, itemsize):
            ranked.append((-ops / nbytes, nbytes, layer, names))
    plan = [{} for _ in layer_types]
    total, fits = 0, True
    for _, nbytes, layer, names in sorted(ranked):
        fits = fits and total + nbytes <= budget
        total += nbytes if fits else 0
        plan[layer].update(dict.fromkeys(names, fits))
    return plan, total


# ------------------------------------------------------------ the modules
def _matrix(in_axis=-2):
    return nn.initializers.lecun_normal(in_axis=in_axis, out_axis=-1,
                                        batch_axis=())


class Lfm2Block(nn.Module):
    sizes: Sizes
    kind: str      # "conv" | "full_attention"
    dense: bool    # a dense gated MLP in place of the expert layer

    @nn.compact
    def __call__(self, h):
        sz = self.sizes
        d, hd = sz.hidden_size, sz.head_dim
        ones = nn.initializers.ones

        def par(name, init, *shape):
            return self.param(name, init, shape, jnp.float32).astype(h.dtype)

        u = rms(h, par("operator_norm", ones, d), sz.norm_eps)
        if self.kind == "conv":
            h = h + short_conv(
                u, par("in_proj", _matrix(), d, 3 * d),
                par("conv_kernel", _matrix(0), sz.conv_L_cache, d),
                par("out_proj", _matrix(), d, d))
        else:
            nq, nkv = sz.num_attention_heads, sz.num_key_value_heads
            p = {"q_proj": par("q_proj", _matrix(), d, nq * hd),
                 "k_proj": par("k_proj", _matrix(), d, nkv * hd),
                 "v_proj": par("v_proj", _matrix(), d, nkv * hd),
                 "o_proj": par("o_proj", _matrix(), nq * hd, d),
                 "q_norm": par("q_norm", ones, hd),
                 "k_norm": par("k_norm", ones, hd)}
            h = h + attention(u, p, sz)
        m = rms(h, par("ffn_norm", ones, d), sz.norm_eps)
        if self.dense:
            # gated_mlp written out: its two outputs are named here and not
            # in the function, which the expert layer's full-size path runs
            # for every held expert
            f = sz.intermediate_size
            h1 = checkpoint_name(m @ par("w1", _matrix(), d, f), "mlp_h1")
            h3 = checkpoint_name(m @ par("w3", _matrix(), d, f), "mlp_h3")
            return h + (jax.nn.silu(h1) * h3) @ par("w2", _matrix(), f, d), \
                None
        f, held = sz.moe_intermediate_size, sz.experts_held[1] - sz.experts_held[0]
        stacked = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                               batch_axis=(0,))
        p = {"router": par("router", _matrix(), d, sz.num_experts),
             "expert_bias": par("expert_bias", nn.initializers.normal(0.01),
                                sz.num_experts),
             "experts_w1": par("experts_w1", stacked, held, d, f),
             "experts_w3": par("experts_w3", stacked, held, d, f),
             "experts_w2": par("experts_w2", stacked, held, f, d)}
        out, stats = expert_layer(m.reshape(-1, d), p, sz)
        return h + out.reshape(h.shape), stats


class Lfm2MoeLM(nn.Module):
    """tokens [B, T] -> logits [B, T, vocab_size]. The fields are the
    model's published keys; ``experts_held`` is the range of the
    ``num_experts`` routed experts whose matrices live here, and
    ``vocab_size`` the rows of the vocabulary held (ids are drawn from
    them)."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    vocab_size: int
    layer_types: Any
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    experts_held: Any = None
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    routed_scaling_factor: float = 1.0
    head_dim: int | None = None
    moe_row_budget: float = 0.75
    moe_tile_rows: int = 256
    attention_query_block: int = 256

    def __post_init__(self):
        # a configuration file hands lists: the fields have to hash
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        held = self.experts_held or (0, self.num_experts)
        object.__setattr__(self, "experts_held", tuple(int(e) for e in held))
        super().__post_init__()

    def sizes(self) -> Sizes:
        names = [f.name for f in dataclasses.fields(Sizes)]
        values = {n: getattr(self, n) for n in names}
        values["head_dim"] = (self.head_dim
                              or self.hidden_size // self.num_attention_heads)
        return Sizes(**values)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        sz = self.sizes()
        lo, hi = sz.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held={sz.experts_held} is no range of "
                             f"the {self.num_experts} routed experts")
        embedding = self.param("embedding", nn.initializers.normal(0.02),
                               (self.vocab_size, self.hidden_size),
                               jnp.float32)
        h = jnp.take(embedding, tokens, axis=0)
        plan, kept_bytes = kept_names(
            sz, self.layer_types, self.num_dense_layers, *tokens.shape,
            h.dtype.itemsize, KEPT_BYTES)
        perf_instrument.record_remat(plan, kept_bytes)
        stats = []
        for i, kind in enumerate(self.layer_types):
            keep = jax.checkpoint_policies.save_only_these_names(
                *(name for name, kept in plan[i].items() if kept))
            h, st = nn.remat(Lfm2Block, policy=keep)(
                sz, kind, i < self.num_dense_layers, name=f"layer_{i}")(h)
            if st is not None:
                stats.append(st)
        if stats and self.is_mutable_collection(STATS):
            self.sow(STATS, "expert_tokens",
                     jnp.stack([s["expert_tokens"] for s in stats]))
            for name in ("rows_real", "rows_dispatched", "fallback_steps"):
                self.sow(STATS, name, sum(s[name] for s in stats))
        out_norm = self.param("out_norm", nn.initializers.ones,
                              (self.hidden_size,), jnp.float32)
        h = rms(h, out_norm.astype(h.dtype), sz.norm_eps)
        return h @ embedding.astype(h.dtype).T
