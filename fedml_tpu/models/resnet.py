"""CIFAR ResNets — resnet56/resnet110 (reference: fedml_api/model/cv/resnet.py:1-268).

The reference uses the classic 3-stage basic-block CIFAR ResNet (He et al.)
with BatchNorm. TPU notes: NHWC layout; the conv widths (16/32/64 channels)
fill an eighth to a half of the MXU's 128 output columns, so every
``nn.Conv`` here is handed ``ops/packed_conv.conv_general_dilated``, which on
a TPU packs 8, 4 or 2 adjacent output pixels into one matmul row in the
kernel gradient of the stride-1 3x3 convolutions (53 of ResNet-56's 57; at
64 channels in the forward pass too; same parameters, same arithmetic) and
is ``lax.conv_general_dilated`` for the rest and everywhere
else. BatchNorm running stats live in the 'batch_stats'
collection and are federated-averaged with the params (the reference
averages the full state_dict including BN buffers, FedAVGAggregator.py:72-80).
``norm='group'`` swaps in GroupNorm — BN-free variant for non-IID robustness.
``norm='none'`` is the normalization-FREE ResNet (reference
fedml_api/model/cv/resnet_wo_bn.py, used in robust-FL experiments where BN
buffers poison the average): Fixup-style blocks — zero-init on each residual
branch's last conv plus learned scalar scale/bias — keep it trainable
without any norm layer, and aggregation touches only true parameters.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.ops.packed_conv import conv_general_dilated

# every convolution of this file: nn.Conv with the packed-or-plain dispatch
Conv = partial(nn.Conv, conv_general_dilated=conv_general_dilated)


class BasicBlock(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    norm: Callable = nn.BatchNorm
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = Conv(self.filters, (3, 3), self.strides, padding="SAME",
                 use_bias=False, dtype=self.dtype)(x)
        y = self.norm(use_running_average=not train)(y)
        y = nn.relu(y)
        y = Conv(self.filters, (3, 3), padding="SAME", use_bias=False,
                 dtype=self.dtype)(y)
        y = self.norm(use_running_average=not train)(y)
        if residual.shape != y.shape:
            residual = Conv(self.filters, (1, 1), self.strides,
                            use_bias=False, dtype=self.dtype)(residual)
            residual = self.norm(use_running_average=not train)(residual)
        return nn.relu(y + residual)


class ResNetCIFAR(nn.Module):
    """depth = 6n+2 (56 -> n=9, 110 -> n=18); 3 stages of n basic blocks.

    ``dtype=jnp.bfloat16`` runs convs in bf16 on the MXU with f32 params
    and f32 norm statistics (flax norm layers keep reductions in f32) —
    the standard TPU mixed-precision recipe, halving activation HBM for
    the cross-silo vmapped-10-client program."""

    depth: int = 56
    num_classes: int = 10
    norm_type: str = "batch"  # 'batch' | 'group'
    dtype: Any = None  # activation/compute dtype; None = float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        assert (self.depth - 2) % 6 == 0, "depth must be 6n+2"
        n = (self.depth - 2) // 6
        dt = self.dtype
        if dt is not None:
            x = x.astype(dt)
        if self.norm_type == "batch":
            norm = partial(nn.BatchNorm, momentum=0.9, epsilon=1e-5, dtype=dt)
        else:
            norm = partial(_GN, num_groups=8, dtype=dt)

        y = Conv(16, (3, 3), padding="SAME",
                 use_bias=(self.norm_type == "none"), dtype=dt)(x)
        if self.norm_type == "batch":
            y = norm(use_running_average=not train)(y)
        elif self.norm_type == "group":
            y = norm()(y)
        y = nn.relu(y)
        for stage, (filters, stride) in enumerate([(16, 1), (32, 2), (64, 2)]):
            for i in range(n):
                s = (stride, stride) if i == 0 else (1, 1)
                if self.norm_type == "batch":
                    y = BasicBlock(filters, s, norm, dtype=dt)(y, train)
                elif self.norm_type == "group":
                    y = _GNBasicBlock(filters, s, dtype=dt)(y, train)
                else:
                    y = _FixupBasicBlock(filters, s, dtype=dt)(y, train)
        # upcast BEFORE the pool: the spatial mean must accumulate in f32,
        # and the pooled output is tiny so this costs no HBM
        y = jnp.mean(y.astype(jnp.float32), axis=(1, 2))
        return nn.Dense(self.num_classes)(y)


class _GN(nn.Module):
    """GroupNorm shim accepting (and ignoring) use_running_average."""

    num_groups: int = 8
    dtype: Any = None

    @nn.compact
    def __call__(self, x, use_running_average: bool = True):
        return nn.GroupNorm(num_groups=min(self.num_groups, x.shape[-1]),
                            dtype=self.dtype)(x)


class _FixupBasicBlock(nn.Module):
    """Norm-free basic block (resnet_wo_bn parity): residual branch is
    conv-relu-conv with the second conv zero-initialized and a learned
    scalar scale + bias, so the block starts as identity and training stays
    stable without normalization."""

    filters: int
    strides: tuple[int, int] = (1, 1)
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        # Fixup scalars are stored f32 (param_dtype default) but applied in
        # the compute dtype so bf16 activations are not promoted back to f32
        cd = self.dtype or x.dtype
        residual = x
        b1 = self.param("bias1", nn.initializers.zeros, (1,))
        y = Conv(self.filters, (3, 3), self.strides, padding="SAME",
                 use_bias=True, dtype=self.dtype)(x + b1.astype(cd))
        y = nn.relu(y)
        b2 = self.param("bias2", nn.initializers.zeros, (1,))
        y = Conv(self.filters, (3, 3), padding="SAME", use_bias=True,
                 kernel_init=nn.initializers.zeros,
                 dtype=self.dtype)(y + b2.astype(cd))
        scale = self.param("scale", nn.initializers.ones, (1,))
        y = y * scale.astype(cd)
        if residual.shape != y.shape:
            residual = Conv(self.filters, (1, 1), self.strides,
                            use_bias=True, dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class _GNBasicBlock(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        gn = lambda c: nn.GroupNorm(num_groups=min(8, c), dtype=self.dtype)
        residual = x
        y = Conv(self.filters, (3, 3), self.strides, padding="SAME",
                 use_bias=False, dtype=self.dtype)(x)
        y = gn(self.filters)(y)
        y = nn.relu(y)
        y = Conv(self.filters, (3, 3), padding="SAME", use_bias=False,
                 dtype=self.dtype)(y)
        y = gn(self.filters)(y)
        if residual.shape != y.shape:
            residual = Conv(self.filters, (1, 1), self.strides,
                            use_bias=False, dtype=self.dtype)(residual)
            residual = gn(self.filters)(residual)
        return nn.relu(y + residual)
