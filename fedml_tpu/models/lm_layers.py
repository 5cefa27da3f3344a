"""Layers that more than one language model here is built from: RMS norm,
rotary positions, the causal depthwise convolution, causal attention in
query blocks, the gated MLP, and the expert layer that is told which
experts it holds, with the shared expert that some families put beside it.

**The expert layer** (``expert_layer``) routes every token over all
``num_experts`` the router scores and keeps the assignments that fall to
its own (``experts_held``, a range of them); what the absent experts would
add is left out, as one device of an expert-parallel deployment leaves it
to the others (there is no exchange here). No token is dropped. Scoring is
the model's static choice:

    sigmoid:  s = sigmoid(m router), float32;  S = top-k of (s + expert_bias);
              w_e = s_e / (sum_S s + 1e-6) * scale      (the bias selects)
    softmax:  p = softmax(m router) over all experts, float32;
              S = top-k of p;  w_e = p_e / sum_S p * scale

    f = sum over e in S and held here of w_e expert_e(m)

Work follows the assignments held: they are sorted by expert into tiles of
``moe_tile_rows`` rows, each tile of one expert, inside a static budget of
``moe_row_budget`` rows a token (the expectation is ``k * held /
num_experts``; the worst case ``k``), and a loop over the tiles multiplies
each by its own expert's matrices. The groups fill the budget from the
front, so the loop runs the tiles that hold rows and gives the tiles past
the last group zeros behind a ``lax.cond``, with no product (a zero row's
outputs and gradients are exact zeros); it stays a ``scan`` of the
budget's static length, and ``obs/work.py`` counts it at the budget. A
step whose tiles do not fit the budget takes, behind a ``lax.cond``, the
exact path of full size: every held expert for every token, weighed by
zero where the token chose another. (Under ``vmap`` a ``cond`` is a
``select`` and both paths run: fold the clients,
``FedAvgConfig.client_fold="scan"``.) XLA ops only; the tile loop, the
dispatch and the combine carry their own backward passes so that all four
row movements are gathers and the experts' gradients accumulate in place.
The layer returns, beside its output, the held experts' token counts, the
rows that held an assignment, the rows computed (the used tiles' rows, or
held x tokens on the full path) and whether the step took the full path:
a model sows them into ``moe_stats`` once a call.

**What a rematerialised block keeps** (``kept_names``). Each model lists,
for a layer, the named outputs of its products that the block's backward
pass reads, as ``block_outputs`` entries (names, bytes, operations): the
bytes the names weigh at one step's static shapes and the forward
operations not run again where they are kept (``product_output``,
``context_output`` and ``expert_outputs`` make the entries the models
share). The rule ranks all layers' entries by the operations saved a byte
kept (ties: the lighter first, then the earlier layer), takes them in that
order while their bytes stay inside the model's budget, and stops at the
first that does not fit; a block that keeps no name takes no policy
(``remat_policy``) and keeps its input alone. Each entry stands alone:
keeping it saves its own products whatever else is kept. So the expert
tiles' three products are one entry (one loop makes them, and it runs again
whole if any of the three is missing), and the experts chosen (int32) are
kept with them: they say which token a kept row belongs to, and nothing
made again may decide that. At a given sequence length the order is the
same for every batch and every entry's bytes grow with the batch, so a
larger batch keeps a subset of what a smaller one keeps, and in the end
nothing (longer sequences move attention's context up, whose products grow
with the keys seen). Under ``vmap`` over K clients the shapes a model sees
are one client's and K times the bytes are kept: fold the clients.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

STATS = "moe_stats"


def rms(x, gain, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta: float, rotary_dim: int | None = None):
    """x [B, T, H, hd], rotate-half pairing over the first ``rotary_dim``
    lanes of each head (the whole head by default; the rest pass through),
    angles in float32."""
    hd, t = x.shape[-1], x.shape[1]
    rot = rotary_dim or hd
    if rot < hd:
        return jnp.concatenate([rope(x[..., :rot], theta), x[..., rot:]], -1)
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    xf = x.astype(jnp.float32)
    rot_x = jnp.concatenate([-x2, x1], -1).astype(jnp.float32)
    return (xf * cos + rot_x * sin).astype(x.dtype)


def causal_conv(x, taps):
    """x [B, T, C] through a depthwise causal convolution, taps [L, C]:
    y_t = sum_j taps[j] x_{t-L+1+j}, zeros before t = 0."""
    n, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + t] for j in range(n))


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


def attend(q, k, v, query_block: int):
    """Causal attention of q [B, T, G, R, hd] over k and v [B, T, G, hd]
    (key/value head g serving query heads g*R .. g*R + R - 1), queries in
    blocks against the keys up to the block's end, so that a step's scores
    never stand whole: [B, T, G, R, hd]."""
    t = q.shape[1]
    qb = min(query_block, t)
    return jnp.concatenate(
        [_attend_block(q[:, i:i + qb], k[:, :i + qb], v[:, :i + qb], i)
         for i in range(0, t, qb)], axis=1)


def context_output(sz, batch: int, seq_len: int, itemsize: int):
    """The ``block_outputs`` entry of ``attend``'s output, ``attn_context``:
    scores and values are two products over the query-key pairs of a
    sequence, each query block against the keys up to its end."""
    qb = min(sz.attention_query_block, seq_len)
    pairs = sum((min(i + qb, seq_len) - i) * min(i + qb, seq_len)
                for i in range(0, seq_len, qb))
    width = sz.num_attention_heads * sz.head_dim
    return (("attn_context",), batch * seq_len * width * itemsize,
            4 * batch * pairs * width)


# ----------------------------------------------------------- expert layer
def gated_mlp(m, w1, w3, w2):
    return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


@jax.named_scope("fed_shared_expert")
def shared_expert(m, w1, w3, w2, gate):
    """The expert every token goes through, weighed by its own sigmoid
    gate: sigmoid(m gate) * gated_mlp(m)."""
    return jax.nn.sigmoid(m @ gate) * gated_mlp(m, w1, w3, w2)


@jax.named_scope("fed_moe_route")
def route(m, router, expert_bias, k: int, scale: float,
          scoring: str = "sigmoid"):
    """(chosen [N, k] expert ids, weights [N, k]) in float32, by the
    scoring the module's text gives."""
    logits = jnp.dot(m.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "sigmoid":  # the bias selects, the scores weigh
        s = jax.nn.sigmoid(logits)
        bias = lax.stop_gradient(expert_bias.astype(jnp.float32))
        _, chosen = lax.top_k(s + bias, k)
    elif scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, chosen = lax.top_k(s, k)
    else:
        raise ValueError(f"unknown scoring {scoring!r}")
    # kept with the tiles' outputs, whose rows lie where THIS choice put
    # them: a backward pass that chose again from hidden states made again
    # can break a near tie the other way, and every later row of that
    # expert's group would then meet another token's kept output
    chosen = checkpoint_name(chosen, "moe_chosen")
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if scoring == "sigmoid":
        w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6) * scale
    else:
        w = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
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


def _zeros_of(fn, *args):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        jax.eval_shape(fn, *args))


@jax.custom_vjp
def tiled_experts(x, w1, w3, w2, tile_expert, n_used):
    """y [rows, D]: each of the first ``n_used`` tiles of ``rows /
    len(tile_expert)`` rows through the gated MLP of its own expert; the
    tiles past them hold padding alone and give zeros, with no product."""
    return _tiled_fwd(x, w1, w3, w2, tile_expert, n_used)[0]


def _tiled_fwd(x, w1, w3, w2, tile_expert, n_used):
    tiles = x.reshape(tile_expert.shape[0], -1, x.shape[-1])

    def products(xt, e):
        h1, h3 = xt @ _pick(w1, e), xt @ _pick(w3, e)
        return (jax.nn.silu(h1) * h3) @ _pick(w2, e), h1, h3

    def one(_, inp):
        i, xt, e = inp
        return None, lax.cond(i < n_used, products,
                              functools.partial(_zeros_of, products), xt, e)

    index = jnp.arange(tile_expert.shape[0], dtype=jnp.int32)
    _, (y, h1, h3) = lax.scan(one, None, (index, tiles, tile_expert))
    tiles = checkpoint_name(tiles, "moe_tiles")
    h1, h3 = checkpoint_name(h1, "moe_h1"), checkpoint_name(h3, "moe_h3")
    y = checkpoint_name(y.reshape(x.shape), "moe_y")
    return y, (tiles, h1, h3, w1, w3, w2, tile_expert, n_used)


def _tiled_bwd(res, dy):
    tiles, h1, h3, w1, w3, w2, tile_expert, n_used = res

    def products(acc, xt, h1t, h3t, dyt, e):
        dw1, dw3, dw2 = acc
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

    # the accumulators go through the cond, updated in place by one branch
    # and passed through by the other: the compiled loop copies neither
    def nothing(acc, *inp):
        return acc, _zeros_of(products, acc, *inp)[1]

    def one(acc, inp):
        i, *inp = inp
        return lax.cond(i < n_used, products, nothing, acc, *inp)

    zeros = tuple(jnp.zeros_like(w) for w in (w1, w3, w2))
    index = jnp.arange(tile_expert.shape[0], dtype=jnp.int32)
    (dw1, dw3, dw2), dx = lax.scan(
        one, zeros,
        (index, tiles, h1, h3, dy.reshape(tiles.shape), tile_expert))
    return dx.reshape(dy.shape), dw1, dw3, dw2, None, None


tiled_experts.defvjp(_tiled_fwd, _tiled_bwd)


def _budgeted(m, chosen, w, w1, w3, w2, plan):
    dest, row_src, tile_expert, n_used = plan
    y = tiled_experts(dispatch(m, dest, row_src), w1, w3, w2, tile_expert,
                      n_used)
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


def budget_rows(sz, n: int) -> int:
    """Rows of the grouped products for ``n`` tokens: ``moe_row_budget`` a
    token, in whole tiles."""
    tile = sz.moe_tile_rows
    return max(math.ceil(sz.moe_row_budget * n / tile), 1) * tile


def expert_outputs(sz, n: int, itemsize: int):
    """``block_outputs`` entries of the expert layer for ``n`` tokens (the
    rule at the end of the module text)."""
    d, f, rows = sz.hidden_size, sz.moe_intermediate_size, budget_rows(sz, n)
    return [(("moe_chosen", "moe_h1", "moe_h3", "moe_y"),
             rows * (2 * f + d) * itemsize + n * sz.num_experts_per_tok * 4,
             6 * rows * d * f),
            (("moe_tiles",), rows * d * itemsize, 0)]  # a gather, no product


def expert_layer(m, p, sz, scoring: str = "sigmoid"):
    """(f [N, D], stats) for m [N, D]: the held experts' part of the
    routed sum. ``p`` holds ``router``, ``experts_w1``, ``experts_w3``,
    ``experts_w2`` and, for sigmoid scoring, ``expert_bias``; ``sz`` the
    sizes ``experts_held``, ``num_experts_per_tok``,
    ``routed_scaling_factor``, ``moe_row_budget`` and ``moe_tile_rows``."""
    lo, hi = sz.experts_held
    n, tile = m.shape[0], sz.moe_tile_rows
    rows = budget_rows(sz, n)
    chosen, w = route(m, p["router"], p.get("expert_bias"),
                      sz.num_experts_per_tok, sz.routed_scaling_factor,
                      scoring)
    with jax.named_scope("fed_moe_experts"):
        dest, row_src, tile_expert, counts, padded = plan_rows(
            chosen, lo, hi, rows, tile)
        fits = padded <= rows
        f = lax.cond(fits, _budgeted, functools.partial(_full_size, lo),
                     m, chosen, w, p["experts_w1"], p["experts_w3"],
                     p["experts_w2"],
                     (dest, row_src, tile_expert, padded // tile))
    counts = counts.astype(jnp.float32)
    stats = {"expert_tokens": counts, "rows_real": jnp.sum(counts),
             "rows_dispatched": jnp.where(fits, padded.astype(jnp.float32),
                                          float((hi - lo) * n)),
             "fallback_steps": 1.0 - fits.astype(jnp.float32)}
    return f, lax.stop_gradient(stats)


# ------------------------------------------- what a block keeps of its pass
def product_output(name: str, n: int, inner: int, width: int, itemsize: int):
    """The ``block_outputs`` entry of one named product output, ``n`` rows
    of ``width`` from an inner width ``inner``."""
    return (name,), n * width * itemsize, 2 * n * inner * width


def kept_names(tables, budget: float):
    """Which named outputs each block keeps for its backward pass: ({name:
    kept} by layer, bytes kept), from ``tables``, a layer's ``block_outputs``
    for each layer (the rule at the end of the module text)."""
    ranked = [(-ops / nbytes, nbytes, layer, names)
              for layer, table in enumerate(tables)
              for names, nbytes, ops in table]
    plan = [{} for _ in tables]
    total, fits = 0, True
    for _, nbytes, layer, names in sorted(ranked):
        fits = fits and total + nbytes <= budget
        total += nbytes if fits else 0
        plan[layer].update(dict.fromkeys(names, fits))
    return plan, total


def remat_policy(kept: dict):
    """The ``nn.remat`` policy of a block that keeps the names ``kept``
    marks; None, the block keeping its input alone, where it marks none."""
    names = [name for name, keep in kept.items() if keep]
    if not names:
        return None
    return jax.checkpoint_policies.save_only_these_names(*names)
