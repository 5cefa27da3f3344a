"""By hand, on the chip: ``chip_scopes.py`` for a cell whose model carries
scopes of its own (``models/lfm2_moe.py``), until ``scope_times.py`` is
wired into ``run.py``.

    python3 benchmark/tests/chip_scopes_lm.py --workload <name> --seed <n>

``scope_times.scope_of`` gives an op to the OUTERMOST of its fixed scopes,
and the whole folded fit sits under ``fed_client_fold``: this sets the
model's own scopes in their place, so that device time reads by expert
routing, expert products, short convolutions and attention, and what is
left (``outside``) is the dense layer, the norms, the head, the loss and the
fold's sums. Then the registry's ``fed_moe_*`` and ``fed_client_fold_total``
families, one JSON line, also in ``chiprun_out/moe_counters_<workload>.json``.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import scope_times  # noqa: E402
from benchmark.tests import chip_scopes  # noqa: E402

MODEL_SCOPES = ("fed_moe_route", "fed_moe_experts", "fed_short_conv",
                "fed_attention", "fed_gather", "fed_server_update")


def main() -> int:
    from fedml_tpu.obs.metrics import REGISTRY

    scope_times.SCOPES = MODEL_SCOPES
    rc = chip_scopes.main()
    snap = REGISTRY.snapshot()
    line = json.dumps({k: v for k, v in snap.items()
                       if k.startswith(("fed_moe", "fed_client_fold",
                                        "fed_program_store_total"))})
    print(line, flush=True)
    workload = sys.argv[sys.argv.index("--workload") + 1]
    with open(os.path.join(chip_scopes.OUT,
                           f"moe_counters_{workload}.json"), "w") as f:
        f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
