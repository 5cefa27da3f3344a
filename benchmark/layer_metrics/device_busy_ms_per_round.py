"""Device time a round: the union of the intervals in which an operation ran
on the chip, over the rounds of the traced window. Device trace."""

NAME = "device_busy_ms_per_round"
UNIT = "ms"
LAYER = "round program"
MOVES = "rounds_per_s"


def read(run: dict):
    tr = run["trace"]
    if not tr or not tr["busy_s"] or not run["rounds"]:
        return None
    return 1000.0 * tr["busy_s"] / run["rounds"]
