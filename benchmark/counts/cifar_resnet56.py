"""Operations and bytes of ``cifar_resnet56`` (group norm, CIFAR shapes)
from its shapes alone."""

from __future__ import annotations

from functools import partial

from . import common


def _layers():
    layers = [("stem", 32 * 32, 9, 3, 16, 32 * 32)]
    cin, side = 16, 32
    for stage, (c, stride) in enumerate(((16, 1), (32, 2), (64, 2))):
        for b in range(9):
            s = stride if b == 0 else 1
            out = side // s
            tag = f"s{stage}b{b}"
            layers.append((f"{tag}.conv1", out * out, 9, cin, c, side * side))
            layers.append((f"{tag}.conv2", out * out, 9, c, c, out * out))
            if b == 0 and (s != 1 or cin != c):
                layers.append((f"{tag}.short", out * out, 1, cin, c,
                               side * side))
            cin, side = c, out
    layers.append(("fc", 1, 1, 64, 10, 1))
    return tuple(layers)


LAYERS = _layers()

forward_flops_per_sample = partial(common.forward_flops_per_sample, LAYERS)
matmul_ops_per_step = partial(common.matmul_ops_per_step, LAYERS)
