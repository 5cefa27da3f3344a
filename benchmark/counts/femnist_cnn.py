"""Operations and bytes of ``femnist_cnn`` from its shapes alone."""

from __future__ import annotations

from functools import partial

from . import common

# (name, output positions, taps, cin, cout, input positions); SAME padding
LAYERS = (
    ("conv1", 28 * 28, 25, 1, 32, 28 * 28),
    ("conv2", 14 * 14, 25, 32, 64, 14 * 14),
    ("fc1", 1, 1, 3136, 512, 1),
    ("fc2", 1, 1, 512, 62, 1),
)

# 24.60 MFLOP a sample
forward_flops_per_sample = partial(common.forward_flops_per_sample, LAYERS)
matmul_ops_per_step = partial(common.matmul_ops_per_step, LAYERS)
