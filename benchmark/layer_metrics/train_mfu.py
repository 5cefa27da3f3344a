"""The whole step's share of the chip's bf16 peak: three times the forward
operations of a sample (``counts/<config>.py``) times the real samples a
second of this run, over chips times peak. Padding slots do not count."""

NAME = "train_mfu"
UNIT = "%"
LAYER = "whole step"
MOVES = "samples_per_s"


def read(run: dict):
    if not run["peaks"] or not run["samples"]:
        return None
    flops = 3.0 * run["counts"].forward_flops_per_sample() * run["samples"]
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / run["elapsed_s"] / peak
