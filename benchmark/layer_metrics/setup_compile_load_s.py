"""Seconds of set-up the round programs spent in the backend: the XLA
compile, or on a persistent-cache hit reading and loading the executable
in its place, summed over the engine's dispatch variants from the compile
observatory's counters (``jax.monitoring`` events).

``run`` has no field for it and ``run.py`` is not this reader's to edit, so
it imports the program's ``perf_instrument`` itself and asks
``setup_phases()``; a program without that function reads as nothing."""

NAME = "setup_compile_load_s"
UNIT = "s"
LAYER = "round program build: compile or cache load"
MOVES = "setup_s"


def read(run: dict):
    from fedml_tpu.obs import perf_instrument

    phases = getattr(perf_instrument, "setup_phases", None)
    return (phases()["compile_or_load_s"] or None) if phases else None
