"""Seconds of set-up the round programs spent being traced to a jaxpr and
lowered to an MLIR module, summed over the engine's dispatch variants from
the compile observatory's counters (``jax.monitoring`` events). What an
exported program would load in place of.

``run`` has no field for it and ``run.py`` is not this reader's to edit, so
it imports the program's ``perf_instrument`` itself and asks
``setup_phases()``; a program without that function reads as nothing."""

NAME = "setup_trace_lower_s"
UNIT = "s"
LAYER = "round program build: trace and lower"
MOVES = "setup_s"


def read(run: dict):
    from fedml_tpu.obs import perf_instrument

    phases = getattr(perf_instrument, "setup_phases", None)
    if phases is None:
        return None
    p = phases()
    return (p["trace_s"] + p["lower_s"]) or None
