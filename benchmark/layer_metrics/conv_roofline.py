"""XLA's convolution ops (the dense layers lower to them too) against their
roofline: the least time the chip could take for the operations and bytes of
the DISPATCHED shapes (padding slots included, since the kernel computes
them; ``counts/<config>.py``), each op at the larger of operations over the
bf16 peak and bytes over the HBM peak, over the summed device time of the
trace's convolution ops."""

NAME = "conv_roofline"
UNIT = "%"
LAYER = "kernels: XLA convolution ops"
MOVES = "samples_per_s"


def least_seconds(run: dict) -> float:
    """Least time of the matrix-shaped ops of all steps dispatched in the
    window, each at whichever of its two bounds is the larger."""
    pk = run["peaks"]
    steps = run["rounds"] * run["clients_per_round"] * run["batches"]
    return steps * sum(max(flops / pk["bf16_flops_per_s"],
                           nbytes / pk["hbm_bytes_per_s"])
                       for _, flops, nbytes in
                       run["counts"].matmul_ops_per_step(run["batch_size"]))


def read(run: dict):
    tr = run["trace"]
    if not tr or not run["peaks"] or not tr.get("conv_s"):
        return None
    return 100.0 * least_seconds(run) / (tr["conv_s"] * run["chips"])
