"""Share of the expert layer's computed rows that held no assignment: 100 x
(1 - real / dispatched) of the program's counter ``fed_moe_rows_total{kind}``
(rows of the grouped products that held a routed token, and rows computed:
the row budget, or the full size where a step overflowed it). The counts are
made on the device and ride out of the block program with its metrics; the
registry reads them when it is asked, which is here, after the window.

``run`` has no field for it and ``run.py`` is not this reader's to edit, so
it imports the program's ``perf_instrument`` itself and asks ``moe_rows()``;
a program without that function, or one that dispatched no row, reads as
nothing."""

NAME = "moe_pad_rows_pct"
UNIT = "%"
LAYER = "expert layer: grouped products"
MOVES = "samples_per_s"


def read(run: dict):
    from fedml_tpu.obs import perf_instrument

    rows = getattr(perf_instrument, "moe_rows", None)
    if rows is None:
        return None
    n = rows()
    if not n["dispatched"]:
        return None
    return 100.0 * (1.0 - n["real"] / n["dispatched"])
