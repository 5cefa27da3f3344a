"""Plain reference of ``cifar_resnet56``: the 3-stage basic-block CIFAR
ResNet of depth 56 (He et al. 2016) with group norm in place of batch norm
(8 groups, eps 1e-6), 10 classes, in straightforward ``jax.numpy`` and
float32. Imports nothing of the program.

stem conv3x3(16) -> GN -> relu; stages of 9 blocks at 16/32/64 channels,
the first block of stages 2 and 3 at stride 2 with a conv1x1 + GN shortcut;
block = conv3x3 -> GN -> relu -> conv3x3 -> GN, + shortcut, relu; mean over
H and W; dense 10. No conv has a bias. The parameter tree carries the names
the program's model publishes, so that one tree serves both sides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import common

GROUPS = 8
EPS = 1e-6
STAGES = ((16, 1), (32, 2), (64, 2))
BLOCKS_PER_STAGE = 9


def _gn_shapes(c):
    return {"scale": (c,), "bias": (c,)}


def _shapes():
    shapes = {"Conv_0": {"kernel": (3, 3, 3, 16)},
              "_GN_0": {"GroupNorm_0": _gn_shapes(16)},
              "Dense_0": {"kernel": (64, 10), "bias": (10,)}}
    cin, i = 16, 0
    for c, stride in STAGES:
        for b in range(BLOCKS_PER_STAGE):
            blk = {"Conv_0": {"kernel": (3, 3, cin, c)},
                   "GroupNorm_0": _gn_shapes(c),
                   "Conv_1": {"kernel": (3, 3, c, c)},
                   "GroupNorm_1": _gn_shapes(c)}
            if b == 0 and (stride != 1 or cin != c):
                blk["Conv_2"] = {"kernel": (1, 1, cin, c)}
                blk["GroupNorm_2"] = _gn_shapes(c)
            shapes[f"_GNBasicBlock_{i}"] = blk
            cin, i = c, i + 1
    return shapes


SHAPES = _shapes()


def _last_norm_scale(names) -> bool:
    return (names[0].startswith("_GNBasicBlock")
            and names[1:] == ("GroupNorm_1", "scale"))


def init_params(key):
    """Weights from the key, on the device: LeCun-normal kernels, unit norm
    scales, zero biases (the program's own defaults), and the scale of the
    last norm of every residual branch at zero (``zero_init_residual`` of
    the source's own model; Goyal et al. 2017). With every scale at one the
    56-layer gradient is ill-conditioned at initialisation: in float32 it
    differs from its float64 twin by 0.7 % on the median leaf and in
    bfloat16 by 45 %, with the zero start by 2e-6 and 8 % (CPU, batch 16,
    PR 25), and no output of a round could tell the two precisions apart."""
    return common.init_tree(SHAPES, key, zero=_last_norm_scale)


def _group_norm(x, p):
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, GROUPS, c // GROUPS)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + EPS)
    return g.reshape(n, h, w, c) * p["scale"] + p["bias"]


def forward(params, x, block_conv=common.conv):
    """Logits ``[n, 10]`` for float32 images ``[n, 32, 32, 3]`` in [0, 1].
    ``block_conv`` computes the 54 3x3 convolutions of the residual
    branches; a control lowers it alone."""
    y = common.conv(x, params["Conv_0"]["kernel"])
    y = jax.nn.relu(_group_norm(y, params["_GN_0"]["GroupNorm_0"]))
    i = 0
    for _, stride in STAGES:
        for b in range(BLOCKS_PER_STAGE):
            p = params[f"_GNBasicBlock_{i}"]
            s = stride if b == 0 else 1
            r = y
            y = block_conv(y, p["Conv_0"]["kernel"], s)
            y = jax.nn.relu(_group_norm(y, p["GroupNorm_0"]))
            y = _group_norm(block_conv(y, p["Conv_1"]["kernel"]),
                            p["GroupNorm_1"])
            if "Conv_2" in p:
                r = _group_norm(common.conv(r, p["Conv_2"]["kernel"], s),
                                p["GroupNorm_2"])
            y = jax.nn.relu(y + r)
            i += 1
    y = jnp.mean(y, axis=(1, 2))
    return y @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]


# the controls of ``correct``: lower-precision forwards put in the
# program's place (benchmark/tests/chip_readings.py reads them on the chip).
# ``block_convs_bf16`` lowers only the residual branches' convolutions, 92 %
# of the cell's device time, and leaves stem, shortcuts, norms and head in
# float32: the check has to see those convolutions by themselves.
CONTROLS = {
    "reference_bf16": common.in_bf16(forward),
    "block_convs_bf16": functools.partial(forward,
                                          block_conv=common.conv_bf16),
}
