"""Seconds of set-up in the engine's own ``init`` span: ``FedAvgAPI``'s
build, ``task.init`` and its compiles included.

``run`` has no field for it and ``run.py`` is not this reader's to edit, so
it imports the program's ``perf_instrument`` itself and asks
``setup_phases()``; a program without that function reads as nothing."""

NAME = "setup_init_s"
UNIT = "s"
LAYER = "engine build"
MOVES = "setup_s"


def read(run: dict):
    from fedml_tpu.obs import perf_instrument

    phases = getattr(perf_instrument, "setup_phases", None)
    return (phases()["init_s"] or None) if phases else None
