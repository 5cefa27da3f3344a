"""Share of the traced window in which no operation ran on the chip."""

NAME = "device_idle_pct"
UNIT = "%"
LAYER = "device"
MOVES = "rounds_per_s"


def read(run: dict):
    tr = run["trace"]
    if not tr or not tr["busy_s"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
