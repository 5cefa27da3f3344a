"""XLA's convolution ops (the dense layers lower to them too) against their
roofline, by the round program's own count of its products: every
``dot_general`` and ``conv_general_dilated`` of the block program at the
runs it makes (forward, made again, gradients), read off its jaxpr where it
is traced and kept with it in the program store
(``fedml_tpu/obs/work.py``). ``conv_roofline``'s formula with that count in
place of ``counts/<config>.py``'s hand count: the least time of the
window's rounds, each op at the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, over the summed device time of the trace's
convolution ops.

``run`` has no field for it and ``run.py`` is not this reader's to edit, so
it imports the program's ``perf_instrument`` itself and asks
``matmul_ops()``, the per-round list; a program without that function, or
one that counted nothing, reads as nothing."""

NAME = "matmul_roofline"
UNIT = "%"
LAYER = "kernels: XLA convolution ops"
MOVES = "samples_per_s"


def least_seconds(run: dict, ops) -> float:
    """Least time of the window's rounds, each op at whichever of its two
    bounds is the larger."""
    pk = run["peaks"]
    return run["rounds"] * sum(max(flops / pk["bf16_flops_per_s"],
                                   nbytes / pk["hbm_bytes_per_s"])
                               for _, _, flops, nbytes in ops)


def read(run: dict):
    from fedml_tpu.obs import perf_instrument

    tr = run["trace"]
    counted = getattr(perf_instrument, "matmul_ops", None)
    if not tr or not run["peaks"] or not tr.get("conv_s") or counted is None:
        return None
    ops = counted()
    if not ops:
        return None
    return 100.0 * least_seconds(run, ops) / (tr["conv_s"] * run["chips"])
