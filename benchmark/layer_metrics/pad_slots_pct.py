"""Share of the dispatched sample slots (clients x batch slots x batch size)
that held no real row. An exact count."""

NAME = "pad_slots_pct"
UNIT = "%"
LAYER = "cohort packer"
MOVES = "samples_per_s"


def read(run: dict):
    if not run["slots"] or run["samples"] >= run["slots"]:
        return None
    return 100.0 * (1.0 - run["samples"] / run["slots"])
