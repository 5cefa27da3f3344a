"""Plain reference of ``femnist_cnn``: the FedAvg paper's CNN (McMahan et
al. 2017; FedML ``CNN_OriginalFedAvg``), 62 classes, in straightforward
``jax.numpy`` and float32. Imports nothing of the program.

conv5x5(32) -> maxpool2 -> relu -> conv5x5(64) -> maxpool2 -> relu ->
flatten (H, W, C order) -> dense 512 -> relu -> dense 62. NHWC images,
HWIO kernels, SAME padding. The parameter tree carries the names the
program's model publishes, so that one tree serves both sides.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

SHAPES = {
    "Conv_0": {"kernel": (5, 5, 1, 32), "bias": (32,)},
    "Conv_1": {"kernel": (5, 5, 32, 64), "bias": (64,)},
    "Dense_0": {"kernel": (3136, 512), "bias": (512,)},
    "Dense_1": {"kernel": (512, 62), "bias": (62,)},
}


def init_params(key):
    """Weights from the key, on the device: LeCun-normal kernels (the
    program's own default), zero biases."""
    return common.init_tree(SHAPES, key)


def _maxpool2(x):
    n, h, w, c = x.shape
    return jnp.max(x.reshape(n, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def forward(params, x):
    """Logits ``[n, 62]`` for float32 images ``[n, 28, 28, 1]`` in [0, 1]."""
    for name in ("Conv_0", "Conv_1"):
        x = common.conv(x, params[name]["kernel"]) + params[name]["bias"]
        x = jax.nn.relu(_maxpool2(x))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["Dense_0"]["kernel"]
                    + params["Dense_0"]["bias"])
    return x @ params["Dense_1"]["kernel"] + params["Dense_1"]["bias"]


# the controls of ``correct``: lower-precision forwards put in the
# program's place (benchmark/tests/chip_readings.py reads them on the chip)
CONTROLS = {"reference_bf16": common.in_bf16(forward)}
