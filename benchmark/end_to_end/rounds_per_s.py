"""Federated rounds completed in the window over the window's real length."""

UNIT = "rounds/s"


def read(run: dict):
    return run["rounds"] / run["elapsed_s"]
