"""Real (unmasked) training rows consumed by the local fits of the window's
rounds, from the engine's returned ``count``, over the same real length."""

UNIT = "samples/s"


def read(run: dict):
    return run["samples"] / run["elapsed_s"]
