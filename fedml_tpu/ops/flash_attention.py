"""Blockwise flash attention as Pallas TPU kernels (fwd + bwd).

The reference has no attention anywhere (its largest sequence model is an
80-char LSTM, model/nlp/rnn.py:4-36); long-context support is a
capability-plus of this framework (SURVEY.md §2.7). The sequence-parallel
layer (fedml_tpu/parallel/ring_attention.py) rotates K/V blocks over ICI and
runs an online-softmax block update per step — this module is that block
update as a proper TPU kernel: Q/K/V tiles staged through VMEM, scores on
the MXU with f32 accumulation, the softmax running max/denominator kept in
registers instead of HBM round-trips.

Layout: [B, T, H, D] in, collapsed to a (B*H, q-block) grid; each program
owns one 128-row query tile and loops over key tiles. Backward follows the
standard flash recurrence (recompute P from the saved logsumexp, then
dV = P^T dO, dS = P*(dP - delta), dQ/dK via dS) as two kernels gridded over
q-tiles (dQ) and k-tiles (dK/dV).

The per-row statistics (logsumexp, delta, the lse cotangent) travel between
the kernels lane-dense as [BH, Tpad/block_q, 1, block_q]: every block's last
two dimensions equal the array's, which is what the TPU lowering requires of
a block that is not a multiple of (8, 128). Inside a kernel a statistic is
a (block_q, 1) column next to the (block_q, block_k) score tile, transposed
on the way in and out.

``_mode`` is the one place that decides what serves a call: the Mosaic
kernels on a TPU (a refusal by the compiler is an error, nothing stands in
for the kernel), the Pallas interpreter or the jnp twin off-TPU (how the CPU
tests see the kernel).

K and V (forward, dQ) or Q and dO (dK/dV) stay whole in VMEM for one
(batch, head), double-buffered, and must fit the 16 MiB a v5e kernel may
use: T <= 15360 in bf16 and T <= 7680 in f32 at D <= 128 (``_check_resident``,
measured against the v5e compiler). Above that the call raises before
lowering; longer sequences go through parallel/ring_attention, which hands
each device a slice.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _sds(shape, dtype, like):
    """ShapeDtypeStruct inheriting ``like``'s varying-manual-axes (vma): a
    pallas_call's out_shape carries no vma by default, which would fail
    shard_map(check_vma=True) at the kernel boundary on TPU."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _mode(x) -> str:
    """Which implementation serves this call.

    - 'pallas' on TPU: the real Mosaic kernels (vma-typed via _sds).
    - 'jnp' off-TPU when the inputs carry varying-manual-axes, i.e. we are
      inside shard_map(check_vma=True): Pallas INTERPRET lowering emulates
      the grid as a while_loop of dynamic_slices whose counters carry no
      vma, so strict vma checking rejects it (an interpreter artifact, not
      a property of the kernels). The jnp path is semantically identical
      (same masking, same lse definition, same lse cotangent) and
      vma-transparent.
    - 'interpret' otherwise (off-TPU, no vma): the Pallas interpreter —
      keeps the kernel logic itself under test on CPU.
    """
    if jax.default_backend() == "tpu":
        return "pallas"
    if getattr(jax.typeof(x), "vma", None):
        return "jnp"
    return "interpret"


# Whole-sequence residency limit, found by asking the v5e compiler (libtpu
# 0.0.34, 16 heads): a kernel may use 16 MiB of VMEM, the two whole operands
# are double-buffered with D padded to 128 lanes, and the tiles and
# temporaries took up to 128 KiB more. Forward, dQ and dK/dV all compiled at
# 15 MiB (bf16 T=15360, f32 T=7680, D <= 128) and were refused at 16 MiB.
MAX_RESIDENT_BYTES = 15 * 1024 * 1024


def _check_resident(Tpad, D, dtype):
    lanes = -(-D // 128) * 128
    need = 4 * Tpad * lanes * jnp.dtype(dtype).itemsize
    if need > MAX_RESIDENT_BYTES:
        raise ValueError(
            f"flash_attention keeps two whole [T, D] operands of one head in "
            f"VMEM: padded T={Tpad}, D={D}, {jnp.dtype(dtype).name} needs "
            f"{need} bytes double-buffered, above the {MAX_RESIDENT_BYTES} "
            "the v5e compiler accepts (bf16: T <= 15360, f32: T <= 7680 at "
            "D <= 128); shard the sequence (parallel/ring_attention) instead")


def _rows(x, block_q):
    """[BH, Tpad] -> the kernels' lane-dense [BH, Tpad/block_q, 1, block_q]."""
    BH, Tpad = x.shape
    return x.reshape(BH, Tpad // block_q, 1, block_q)


def _mask(scores, q0, k0, bq, bk, seq_len, causal):
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = kpos < seq_len
    if causal:
        ok = jnp.logical_and(ok, kpos <= qpos)
    return jnp.where(ok, scores, NEG_INF)


# ----------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, seq_len,
                causal, scale):
    bq, d = q_ref.shape[1], q_ref.shape[2]
    qi = pl.program_id(1)
    q0 = qi * bq
    q = q_ref[0].astype(jnp.float32)

    nk = k_ref.shape[1] // block_k

    def body(j, carry):
        o, l, m = carry  # (bq, d), (bq, 1), (bq, 1)
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(k0, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(k0, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = _mask(s, q0, k0, bq, block_k, seq_len, causal)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return o_new, l_new, m_new

    o0 = jnp.zeros((bq, d), jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    o, l, m = jax.lax.fori_loop(0, nk, body, (o0, l0, m0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l_safe)).T


def _dense_mask(s, seq_len, causal):
    """The kernels' _mask on the full [BH, Tpad, Tpad] score tensor."""
    Tq, Tk = s.shape[-2], s.shape[-1]
    qpos = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
    ok = kpos < seq_len
    if causal:
        ok = jnp.logical_and(ok, kpos <= qpos)
    return jnp.where(ok[None], s, NEG_INF)


def _dense_fwd(qf, kf, vf, seq_len, causal, scale):
    """jnp twin of _fwd_kernel on the padded [BH, Tpad, D] layout: same
    masking, same l_safe floor, same lse = m + log(l) definition."""
    s = jnp.einsum("btd,bsd->bts", qf.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    s = _dense_mask(s, seq_len, causal)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.maximum(l, 1e-30)
    o = jnp.einsum("bts,bsd->btd", p, vf.astype(jnp.float32)) / l_safe[..., None]
    return o.astype(qf.dtype), m + jnp.log(l_safe)


def _dense_bwd(qf, kf, vf, dof, lse, delta, glse, seq_len, causal, scale):
    """jnp twin of the two backward kernels (recompute-P flash recurrence)."""
    f32 = jnp.float32
    s = jnp.einsum("btd,bsd->bts", qf.astype(f32), kf.astype(f32)) * scale
    s = _dense_mask(s, seq_len, causal)
    p = jnp.exp(s - lse[..., None])
    do = dof.astype(f32)
    dv = jnp.einsum("bts,btd->bsd", p, do)
    dp = jnp.einsum("btd,bsd->bts", do, vf.astype(f32))
    ds = p * (dp + (glse - delta)[..., None])
    dq = jnp.einsum("bts,bsd->btd", ds, kf.astype(f32)) * scale
    dk = jnp.einsum("bts,btd->bsd", ds, qf.astype(f32)) * scale
    return dq.astype(qf.dtype), dk.astype(kf.dtype), dv.astype(vf.dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k):
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    blk = math.lcm(block_q, block_k)
    Tpad = -(-T // blk) * blk

    def prep(x):
        x = jnp.moveaxis(x, 2, 1).reshape(B * H, T, D)  # [BH, T, D]
        return jnp.pad(x, ((0, 0), (0, Tpad - T), (0, 0)))

    qf, kf, vf = prep(q), prep(k), prep(v)
    mode = _mode(q)
    if mode == "jnp":
        o, lse = _dense_fwd(qf, kf, vf, T, causal, scale)
    else:
        o, lse = _pallas_fwd(qf, kf, vf, T, causal, scale, block_q, block_k,
                             interpret=mode == "interpret")
    return o, lse


def _pallas_fwd(qf, kf, vf, T, causal, scale, block_q, block_k, interpret):
    BH, Tpad, D = qf.shape
    _check_resident(Tpad, D, kf.dtype)
    nq = Tpad // block_q
    tile = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0), memory_space=pltpu.VMEM)
    whole = pl.BlockSpec((1, Tpad, D), lambda b, i: (b, 0, 0), memory_space=pltpu.VMEM)
    row = pl.BlockSpec((1, 1, 1, block_q), lambda b, i: (b, i, 0, 0), memory_space=pltpu.VMEM)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, seq_len=T,
                          causal=causal, scale=scale),
        out_shape=(
            _sds((BH, Tpad, D), qf.dtype, qf),
            _sds((BH, nq, 1, block_q), jnp.float32, qf),
        ),
        grid=(BH, nq),
        in_specs=[tile, whole, whole],
        out_specs=(tile, row),
        interpret=interpret,
    )(qf, kf, vf)
    return o, lse.reshape(BH, Tpad)


# ---------------------------------------------------------------- backward
# ds_ij = p_ij * (dp_ij - delta_i + glse_i): the last term is the cotangent
# of the lse output (dlse_i/ds_ij = p_ij), zero when only `out` is used.
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
                   dq_ref, *, block_k, seq_len, causal, scale):
    bq = q_ref.shape[1]
    qi = pl.program_id(1)
    q0 = qi * bq
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0].T
    corr = (glse_ref[0, 0] - delta_ref[0, 0]).T
    nk = k_ref.shape[1] // block_k

    def body(j, dq):
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(k0, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(k0, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = _mask(s, q0, k0, bq, block_k, seq_len, causal)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp + corr)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros_like(q))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, glse_ref,
                    dk_ref, dv_ref, *, block_q, seq_len, causal, scale):
    bk = k_ref.shape[1]
    ki = pl.program_id(1)
    k0 = ki * bk
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    nq = q_ref.shape[1] // block_q

    def body(i, carry):
        dk, dv = carry
        q0 = pl.multiple_of(i * block_q, block_q)
        q = q_ref[0, pl.ds(q0, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(q0, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, i].T
        corr = (glse_ref[0, i] - delta_ref[0, i]).T
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = _mask(s, q0, k0, block_q, bk, seq_len, causal)
        p = jnp.exp(s - lse)
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp + corr)
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale
        return dk, dv

    dk, dv = jax.lax.fori_loop(0, nq, body, (jnp.zeros_like(k), jnp.zeros_like(v)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ------------------------------------------------------------------ public
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal: bool = False, block_q: int = 128,
                             block_k: int = 128):
    """flash attention returning (out [B,T,H,D], lse [B,H,T]).

    The per-row logsumexp is a first-class output with a correct cotangent
    (folded into the backward kernels), so downstream code may use it —
    ring attention merges per-rotation partials as
    out = w1*out1 + w2*out2, w_i = exp(lse_i - logaddexp(lse1, lse2))
    (parallel/ring_attention.ring_attention_flash) and gradients stay exact.
    """
    out, (_, lse) = _flash_call(q, k, v, causal, block_q, block_k)
    B, T, H, D = q.shape
    return out, lse[:, :T].reshape(B, H, T)


def flash_attention(q, k, v, causal: bool = False, block_q: int = 128,
                    block_k: int = 128):
    """softmax(QK^T/sqrt(D))V with O(T) memory. [B, T, H, D] in/out.

    Equivalent to parallel/ring_attention.full_attention; pads T internally
    to the block size, so any sequence length works.
    """
    return flash_attention_with_lse(q, k, v, causal, block_q, block_k)[0]


def _flash_call(q, k, v, causal, block_q, block_k):
    B, T, H, D = q.shape
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k)
    out = jnp.moveaxis(o[:, :T].reshape(B, H, T, D), 1, 2)
    return out, (o, lse)


def _fwd_rule(q, k, v, causal, block_q, block_k):
    out, (o, lse) = _flash_call(q, k, v, causal, block_q, block_k)
    B, T, H, D = q.shape
    return (out, lse[:, :T].reshape(B, H, T)), (q, k, v, o, lse)


def _bwd_rule(causal, block_q, block_k, res, gs):
    g, g_lse = gs
    q, k, v, o, lse = res
    B, T, H, D = q.shape
    scale = 1.0 / (D ** 0.5)
    Tpad = o.shape[1]
    BH = B * H

    def prep(x):
        x = jnp.moveaxis(x, 2, 1).reshape(BH, T, D)
        return jnp.pad(x, ((0, 0), (0, Tpad - T), (0, 0)))

    qf, kf, vf = prep(q), prep(k), prep(v)
    dof = prep(g)
    # delta_i = sum_d dO_i O_i (the rowwise correction of the softmax vjp)
    delta = jnp.sum(dof.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # lse cotangent, padded back to [BH, Tpad] (zeros on out-only use)
    glse = jnp.pad(g_lse.astype(jnp.float32).reshape(BH, T),
                   ((0, 0), (0, Tpad - T)))

    mode = _mode(q)
    if mode == "jnp":
        grads = _dense_bwd(qf, kf, vf, dof, lse, delta, glse, T, causal, scale)
    else:
        grads = _pallas_bwd(qf, kf, vf, dof, lse, delta, glse, T, causal,
                            scale, block_q, block_k,
                            interpret=mode == "interpret")
    return tuple(jnp.moveaxis(x[:, :T].reshape(B, H, T, D), 1, 2)
                 for x in grads)


def _pallas_bwd(qf, kf, vf, dof, lse, delta, glse, T, causal, scale,
                block_q, block_k, interpret):
    BH, Tpad, D = qf.shape
    _check_resident(Tpad, D, qf.dtype)
    nq = Tpad // block_q
    stats = [_rows(x, block_q) for x in (lse, delta, glse)]
    vmem = pltpu.VMEM
    whole = pl.BlockSpec((1, Tpad, D), lambda b, i: (b, 0, 0), memory_space=vmem)
    q_tile = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0), memory_space=vmem)
    k_tile = pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0), memory_space=vmem)
    row = pl.BlockSpec((1, 1, 1, block_q), lambda b, i: (b, i, 0, 0), memory_space=vmem)
    rows = pl.BlockSpec((1, nq, 1, block_q), lambda b, i: (b, 0, 0, 0), memory_space=vmem)

    dqf = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, seq_len=T,
                          causal=causal, scale=scale),
        out_shape=_sds((BH, Tpad, D), qf.dtype, qf),
        grid=(BH, nq),
        in_specs=[q_tile, whole, whole, q_tile, row, row, row],
        out_specs=q_tile,
        interpret=interpret,
    )(qf, kf, vf, dof, *stats)

    dkf, dvf = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, seq_len=T,
                          causal=causal, scale=scale),
        out_shape=(
            _sds((BH, Tpad, D), kf.dtype, kf),
            _sds((BH, Tpad, D), vf.dtype, vf),
        ),
        grid=(BH, Tpad // block_k),
        in_specs=[whole, k_tile, k_tile, whole, rows, rows, rows],
        out_specs=(k_tile, k_tile),
        interpret=interpret,
    )(qf, kf, vf, dof, *stats)
    return dqf, dkf, dvf


flash_attention_with_lse.defvjp(_fwd_rule, _bwd_rule)
