"""What the configurations' counts share. A matrix-shaped layer is
(name, output positions a sample, taps, cin, cout, input positions a sample):
a 5x5 conv at 28x28 is 784 positions of a 25-tap contraction; a dense layer is
one position, one tap."""

from __future__ import annotations

BYTES = 4  # float32 activations, weights and gradients


def forward_flops_per_sample(layers) -> float:
    """Multiply-adds counted as two; norms, ReLUs and pools left out."""
    return float(sum(2 * pos * k * cin * cout
                     for _, pos, k, cin, cout, _ in layers))


def matmul_ops_per_step(layers, batch_size: int):
    """The matrix-shaped device ops of ONE client's local step on a batch of
    ``batch_size`` slots (padding included: the kernel computes them), as
    (name, flops, bytes): forward, gradient of the weights and, past the
    first layer, gradient of the input. Weights are the client's own copy,
    so each op reads or writes them once."""
    ops = []
    for i, (name, pos, k, cin, cout, in_pos) in enumerate(layers):
        flops = 2.0 * batch_size * pos * k * cin * cout
        nbytes = BYTES * (batch_size * in_pos * cin + batch_size * pos * cout
                          + k * cin * cout)
        ops.append((f"{name}.fwd", flops, nbytes))
        ops.append((f"{name}.dw", flops, nbytes))
        if i:
            ops.append((f"{name}.dx", flops, nbytes))
    return ops
