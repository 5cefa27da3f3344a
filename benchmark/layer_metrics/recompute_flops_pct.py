"""Share of the round program's matrix FLOPs that a rematerialised region
makes again in the backward pass to save the memory of keeping its values:
100 x the ``run="recompute"`` FLOPs over the FLOPs of every run, from the
program's own count (``fedml_tpu/obs/work.py``: counted where the block
program is traced, kept with it in the program store).

``run`` has no field for it and ``run.py`` is not this reader's to edit, so
it imports the program's ``perf_instrument`` itself and asks
``matmul_ops()``; a program without that function, or one that counted
nothing, reads as nothing."""

NAME = "recompute_flops_pct"
UNIT = "%"
LAYER = "round program"
MOVES = "samples_per_s"


def read(run: dict):
    from fedml_tpu.obs import perf_instrument

    counted = getattr(perf_instrument, "matmul_ops", None)
    if counted is None:
        return None
    ops = counted()
    total = sum(flops for _, _, flops, _ in ops)
    if not total:
        return None
    return 100.0 * sum(flops for _, run_of, flops, _ in ops
                       if run_of == "recompute") / total
