"""Share of the convolution call sites traced so far that the width-packed
convolution served (``ops/packed_conv.py``: adjacent output pixels share the
MXU's output columns), from the program's counter
``fed_conv_sites_total{path}``. The mechanism engages while a program is
traced, so that is where it is counted; every trace of the model counts all
of its ``nn.Conv`` sites, so the share is that of one model: 53 of
ResNet-56's 57.

``run`` has no field for it and ``run.py`` is not this reader's to edit, so
it imports the program's ``perf_instrument`` itself and asks
``conv_sites()``; a program without that function, or one that traced no
site, reads as nothing."""

NAME = "conv_packed_pct"
UNIT = "%"
LAYER = "kernels: XLA convolution ops"
MOVES = "samples_per_s"


def read(run: dict):
    from fedml_tpu.obs import perf_instrument

    sites = getattr(perf_instrument, "conv_sites", None)
    if sites is None:
        return None
    n = sites()
    total = n["packed"] + n["plain"]
    return 100.0 * n["packed"] / total if total else None
