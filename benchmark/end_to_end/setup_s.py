"""Process start to the first timed dispatch: population and weights from
the seed, engine build, the first rounds (trace, lower, compile or cache
hit), which are also what the reference follows."""

UNIT = "s"


def read(run: dict):
    return run["setup_s"]
