"""Host time in the engine's own ``pack`` span (cohort sampling, row
shuffles, index block, placement) a round of the window. Host clock."""

NAME = "pack_ms_per_round"
UNIT = "ms"
LAYER = "round engine host path"
MOVES = "rounds_per_s"


def read(run: dict):
    pack = run["spans_s"].get("pack")
    if not pack or not run["rounds"]:
        return None
    return 1000.0 * pack / run["rounds"]
