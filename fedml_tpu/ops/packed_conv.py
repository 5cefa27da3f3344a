"""Width-packed 3x3 convolutions: adjacent output pixels share the MXU's
128 output columns.

A 3x3 convolution from C to C channels is a matmul ``[pixels, 9C] x
[9C, C]``, and its kernel gradient one with ``9C`` rows, C columns and all
the pixels of a batch to contract over: at C = 16 an eighth of a 128x128
array's columns. P output pixels that sit side by side read a 3 x (P+2)
window, not P windows of 3x3, so one convolution with a
``[3, P+2, Cin, P*Cout]`` kernel at stride ``(1, P)`` computes all P at once
and fills ``P*Cout`` columns::

    w'[kh, j, ci, p*Cout + co] = w[kh, j - p, ci, co]  if 0 <= j - p < 3 else 0
    y' = conv(x, w', window_strides=(1, P), padding=((1, 1), (1, 1)))
    y  = y'.reshape(B, H, W, Cout)

Every product of the plain convolution is there once and the rest are
products with exact zeros: the result is the plain convolution's up to the
order of the float32 sums. ``w'`` is built inside the step from the current
``[3, 3, Cin, Cout]`` kernel, so parameters keep their shapes.

What the v5e showed (PERF.md section 6, PR 27): the kernel gradient, 46 %
of the plain ResNet-56 step, is 1.3 to 2.3 times faster packed until the
columns are full; the forward pass and the input gradient, which XLA
already runs well at 16 channels, gain only where the zeros are few
(P = 2: 4/3 of the products). ``pack_factor`` is that rule and the only
place that holds it.

The packed kernel gradient needs one of its two operands as
``[.., W/P, P*C]``, which under ``vmap`` is a copy and no bitcast. In the
ResNet's stages 1 and 2 that operand is the saved input and not ``dy``
(``grad_lays_out``), so that the backward of whatever wrote ``dy`` stays
fused into the gradient convolutions (PERF.md section 6, PR 30).

``conv_general_dilated`` has ``lax.conv_general_dilated``'s signature and is
handed to ``nn.Conv(conv_general_dilated=...)``; calls the rule does not
pack go to ``lax.conv_general_dilated`` as they came. The call forwards the
``precision`` it was given and sets none of its own.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from fedml_tpu.obs import perf_instrument

MXU_COLUMNS = 128
SCOPE = "fed_conv_packed"
# flax's NHWC activations, HWIO kernel
_NHWC = lax.ConvDimensionNumbers((0, 3, 1, 2), (3, 2, 0, 1), (0, 3, 1, 2))
_SAME_3X3 = ((1, 1), (1, 1))


def pack_factor(kernel_shape, strides, width: int, platform: str,
                grad: bool = False) -> int:
    """Output pixels packed side by side for an HWIO ``kernel_shape`` at a
    ``width`` of W pixels: 1 (the plain call) unless the kernel is 3x3 at
    stride 1 and the program is traced for a TPU. There, for the kernel
    gradient (``grad``), the largest power of two P with
    ``P * Cout <= 128`` and ``W % P == 0``; for the forward pass (and the
    input gradient, a forward pass with the flipped kernel) the same P where
    it is 2 and fills the 128 columns, else 1: the packed kernel holds
    ``(P+2)/3`` times the plain one's products, which the forward pass wins
    back at 4/3 and not above."""
    if platform != "tpu" or tuple(kernel_shape[:2]) != (3, 3) \
            or tuple(strides) != (1, 1):
        return 1
    p = 1
    while 2 * p * kernel_shape[3] <= MXU_COLUMNS and width % (2 * p) == 0:
        p *= 2
    full = p * kernel_shape[3] == MXU_COLUMNS
    return p if grad or (p == 2 and full) else 1


def grad_lays_out(kernel_shape, p: int, p_grad: int) -> str:
    """The operand that a site's kernel gradient lays out again as
    ``[.., W/P, P*C]``: ``"none"`` where it is not packed, else ``"x"``, the
    saved input, where ``Cin == Cout`` and the forward pass is plain
    (``p == 1``), and ``"dy"`` elsewhere.

    The kernel gradient is symmetric in its operands::

        dW[kh,kw,ci,co] = sum x[b,h+kh-1,w+kw-1,ci] * dy[b,h,w,co]
                        = G[2-kh,2-kw,co,ci]

    with ``G`` the kernel gradient of ``conv(dy, K)``, ``K`` of shape
    ``[3,3,Cout,Cin]``, at the cotangent ``x``. Taken so, the packed call
    reshapes ``x``, which is in memory anyway, and fills ``P*Cin`` columns,
    and ``dy`` reaches both gradient convolutions as the layer above wrote
    it, so XLA fuses that layer's backward (a norm's) into them as it does
    for a plain convolution; with ``dy`` reshaped that backward is a pass
    of its own. ``p_grad`` is sized by ``Cout``, so the stem (3 to 16
    channels: 24 columns from ``x``) keeps ``dy``; and where the forward
    pass and the input gradient are packed too (64 channels) the v5e ran
    the ``dy`` form faster (PERF.md section 6, PR 30)."""
    if p_grad == 1:
        return "none"
    return "x" if kernel_shape[2] == kernel_shape[3] and p == 1 else "dy"


def pack_kernel(w, p: int):
    """``[3, 3, Cin, Cout]`` to ``[3, P+2, Cin, P*Cout]``: output pixel q of
    a pack sees the kernel shifted q columns to the right."""
    return jnp.concatenate(
        [jnp.pad(w, ((0, 0), (q, p - 1 - q), (0, 0), (0, 0)))
         for q in range(p)], axis=-1)


@partial(jax.jit, static_argnums=(2, 3))
def _packed(x, w, p: int, precision):
    """The convolution with P output pixels a matmul row; the plain one at
    P = 1. Jitted so that a model's many sites of one shape are traced
    once (set-up time: PERF.md section 6, PR 27)."""
    if p == 1:
        return lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                        dimension_numbers=_NHWC,
                                        precision=precision)
    b, h, width, _ = x.shape
    y = lax.conv_general_dilated(x, pack_kernel(w, p), (1, p), _SAME_3X3,
                                 dimension_numbers=_NHWC, precision=precision)
    return y.reshape(b, h, width, w.shape[-1])


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def packed_conv3x3(x, w, p: int, p_grad: int, precision=None):
    """``x [B,H,W,Cin] * w [3,3,Cin,Cout]`` at stride 1 with ``SAME``
    padding: ``p`` output pixels a matmul row in the forward pass,
    ``p_grad`` in the kernel gradient; both divide W."""
    with jax.named_scope(SCOPE):
        return _packed(x, w, p, precision)


def _fwd(x, w, p, p_grad, precision):
    return packed_conv3x3(x, w, p, p_grad, precision), (x, w)


def _bwd(p, p_grad, precision, res, dy):
    """The input gradient of a 3x3 stride-1 convolution is the same kind of
    convolution of ``dy`` with the flipped, transposed kernel, so it goes
    through the same call under the same rule (autodiff's form of a packed
    call dilates ``dy`` by P instead). The kernel gradient is autodiff's of
    a call packed by ``p_grad``, which contracts over the pixels with 128
    columns filled and reaches the kernel through the pad-and-concatenate;
    ``grad_lays_out`` says which of the two calls it is taken of, and so
    which operand is reshaped to ``[.., W/P, P*C]`` for it."""
    x, w = res
    wt = jnp.flip(w, (0, 1)).swapaxes(2, 3)
    pt = pack_factor(wt.shape, (1, 1), dy.shape[2], "tpu")
    with jax.named_scope(SCOPE):
        dx = _packed(dy, wt, pt, precision)
        if grad_lays_out(w.shape, p, p_grad) == "x":
            g, = jax.vjp(lambda k: _packed(dy, k, p_grad, precision), wt)[1](x)
            dw = jnp.flip(g, (0, 1)).swapaxes(2, 3)
        else:
            dw, = jax.vjp(lambda k: _packed(x, k, p_grad, precision), w)[1](dy)
    return dx, dw


packed_conv3x3.defvjp(_fwd, _bwd)


def conv_general_dilated(lhs, rhs, window_strides, padding,
                         lhs_dilation=None, rhs_dilation=None,
                         dimension_numbers=None, feature_group_count=1,
                         batch_group_count=1, precision=None,
                         preferred_element_type=None):
    """``lax.conv_general_dilated``, with the kernel gradient (and where the
    rule says so the forward pass) packed for an NHWC/HWIO 3x3 call at
    stride 1 with ``SAME`` padding, undilated and ungrouped, traced for a
    TPU."""
    p = p_grad = 1
    if (dimension_numbers == _NHWC and lhs.ndim == 4
            and (padding == "SAME" or tuple(map(tuple, padding)) == _SAME_3X3)
            and all(d in (None, 1) for d in (*(lhs_dilation or ()),
                                             *(rhs_dilation or ())))
            and feature_group_count == batch_group_count == 1
            and preferred_element_type is None):
        platform = jax.default_backend()
        p = pack_factor(rhs.shape, window_strides, lhs.shape[2], platform)
        p_grad = pack_factor(rhs.shape, window_strides, lhs.shape[2],
                             platform, grad=True)
    perf_instrument.record_conv_site(
        p_grad, grad_lays_out(rhs.shape, p, p_grad))
    if p_grad == 1:
        return lax.conv_general_dilated(
            lhs, rhs, window_strides, padding, lhs_dilation=lhs_dilation,
            rhs_dilation=rhs_dilation, dimension_numbers=dimension_numbers,
            feature_group_count=feature_group_count,
            batch_group_count=batch_group_count, precision=precision,
            preferred_element_type=preferred_element_type)
    return packed_conv3x3(lhs, rhs, p, p_grad, precision)
