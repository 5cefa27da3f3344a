"""Host time in the engine's own ``place`` span a round of the window: the
engine issuing a round batch's host-to-device transfers on the per-round
path (``run_round``). A child of ``pack``, so it is part of
``pack_ms_per_round`` too. Issue time on the host's clock, not the DMA's own
time. No entry in ``BENCHMARK.json`` yet: the scanned block has no such span
(PERF.md section 6, PR 26), and no per-round cell exists."""

NAME = "place_ms_per_round"
UNIT = "ms"
LAYER = "round engine host path"
MOVES = "rounds_per_s"


def read(run: dict):
    place = run["spans_s"].get("place")
    if not place or not run["rounds"]:
        return None
    return 1000.0 * place / run["rounds"]
