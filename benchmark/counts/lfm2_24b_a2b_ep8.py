"""Operations and bytes of ``lfm2_24b_a2b_ep8`` from its shapes alone.

A "sample" is what the engine's ``count`` counts, which under the sequence
task is a counted TOKEN: ``forward_flops_per_sample()`` is a token's forward
pass (attention over the ``seq_len`` of the configuration's population,
causal: half the square). ``matmul_ops_per_step(batch_size)`` takes
SEQUENCES, as ``run.py`` hands it the engine's batch size.

What a token REQUIRES (``forward_flops_per_sample``, read by ``train_mfu``):
every dense matrix once, the head, causal attention, the router, and the
held experts at the expectation of the routing, ``k * held / num_experts``
experts a token (0.5 here; the real share is in ``fed_moe_rows_total``).

What a step RUNS (``matmul_ops_per_step``, read by ``conv_roofline``): the
matrix-shaped device ops at the DISPATCHED shapes, as many times as they run.
A matrix inside a rematerialised block runs forward twice, then once for
each gradient; the head, outside, forward once. Attention takes its queries
in blocks against the keys up to the block's end, makes each block's scores
three times (the pass, the block's recomputation, its own checkpoint's) and
runs four products backward. The expert layer's products run tile by tile
over the ROW BUDGET, padding rows included, three forward (twice) and six
backward each tile, every one reading its expert's matrix again: they are
bound by bytes, not operations, at this tile height.
"""

from __future__ import annotations

import math

from .. import cells

BYTES = 4  # float32 activations, weights and gradients


_CFG = cells._json("configs", "lfm2_24b_a2b_ep8.json")
SIZES = _CFG["model"]["kwargs"]
SEQ_LEN = int(_CFG["population"]["seq_len"])


def _head_dim(sz):
    return sz.get("head_dim") or sz["hidden_size"] // sz["num_attention_heads"]


def layer_matrices(sz: dict, kind: str, dense: bool):
    """(name, fan_in, fan_out) of the dense matrices every token of a layer
    goes through (the experts' apart)."""
    d, hd = sz["hidden_size"], _head_dim(sz)
    if kind == "conv":
        mats = [("in_proj", d, 3 * d), ("out_proj", d, d)]
    else:
        nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
        mats = [("q_proj", d, nq * hd), ("k_proj", d, nkv * hd),
                ("v_proj", d, nkv * hd), ("o_proj", nq * hd, d)]
    if dense:
        f = sz["intermediate_size"]
        mats += [("w1", d, f), ("w3", d, f), ("w2", f, d)]
    else:
        mats += [("router", d, sz["num_experts"])]
    return mats


def held_share(sz: dict) -> float:
    """Expected held experts a token: k * held / num_experts."""
    lo, hi = sz["experts_held"]
    return sz["num_experts_per_tok"] * (hi - lo) / sz["num_experts"]


def forward_flops_per_sample(sizes: dict | None = None,
                             seq_len: int | None = None) -> float:
    """A token's forward pass; multiply-adds counted as two, norms, gates,
    the short convolution's taps and the softmax left out."""
    sz, t = sizes or SIZES, seq_len or SEQ_LEN
    d, hd = sz["hidden_size"], _head_dim(sz)
    macs = sz["vocab_size"] * d  # the tied head
    for i, kind in enumerate(sz["layer_types"]):
        dense = i < sz["num_dense_layers"]
        macs += sum(k * n for _, k, n in layer_matrices(sz, kind, dense))
        if kind != "conv":  # scores and values over (t + 1) / 2 keys
            macs += 2 * sz["num_attention_heads"] * hd * (t + 1) / 2
        if not dense:
            macs += held_share(sz) * 3 * d * sz["moe_intermediate_size"]
    return 2.0 * macs


def budget_rows(sz: dict, tokens: int) -> int:
    """The expert layer's static row budget for a step of ``tokens``."""
    tile = sz["moe_tile_rows"]
    return max(math.ceil(sz["moe_row_budget"] * tokens / tile), 1) * tile


def _op(name, m, k, n, runs, batch=1):
    return (name, 2.0 * batch * m * k * n * runs,
            float(BYTES * batch * (m * k + k * n + m * n) * runs))


def matmul_ops_per_step(batch_size: int, sizes: dict | None = None,
                        seq_len: int | None = None):
    """The matrix-shaped device ops of ONE client's local step on a batch of
    ``batch_size`` sequences, as (name, flops, bytes), each counted as often
    as it runs (module text)."""
    sz, t = sizes or SIZES, seq_len or SEQ_LEN
    d, hd, tokens = sz["hidden_size"], _head_dim(sz), batch_size * t
    ops = [_op("head", tokens, d, sz["vocab_size"], 3)]
    for i, kind in enumerate(sz["layer_types"]):
        dense = i < sz["num_dense_layers"]
        for name, k, n in layer_matrices(sz, kind, dense):
            ops.append(_op(f"layer_{i}.{name}", tokens, k, n, 4))
        if kind != "conv":
            nkv = sz["num_key_value_heads"]
            rep = sz["num_attention_heads"] // nkv
            qb = min(sz["attention_query_block"], t)
            for first in range(0, t, qb):
                # scores and values: the same shape turned round
                ops.append(_op(f"layer_{i}.attend@{first}", rep * qb, hd,
                               first + qb, 10, batch=batch_size * nkv))
        if not dense:
            tile, f = sz["moe_tile_rows"], sz["moe_intermediate_size"]
            tiles = budget_rows(sz, tokens) // tile
            ops.append(_op(f"layer_{i}.experts", tile, d, f, 12 * tiles))
    return ops
