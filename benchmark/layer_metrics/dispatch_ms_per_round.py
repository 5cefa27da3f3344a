"""Host time in the engine's own ``round`` span a round of the window: the
jit call of each dispatch unit, which inside the window is the enqueue
alone (the harness refuses a window that compiled). Host clock."""

NAME = "dispatch_ms_per_round"
UNIT = "ms"
LAYER = "round engine host path"
MOVES = "rounds_per_s"


def read(run: dict):
    dispatch = run["spans_s"].get("round")
    if not dispatch or not run["rounds"]:
        return None
    return 1000.0 * dispatch / run["rounds"]
