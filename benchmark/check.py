"""The comparison that decides ``correct``: what the timed path produced in
its first rounds against the plain reference's, number by number, each
against a limit of its own (``limits/<workload>.json``).

Numbers, for a reference that followed ``n`` rounds:

  loss_r<i>   |program's mean training loss of round i - reference's| over
              the reference's, for each round followed
  dparam      the change of the global model over the rounds followed, by the
              worst leaf: |norm of the program's change - norm of the
              reference's| over the reference's norm of that leaf or of the
              median leaf, whichever is larger
  dparam_med  the same gap by the median leaf, which the noise of one small
              leaf does not move
  grad1       as ``dparam`` for the change over the first round alone (the
              aggregate the server's update gets), where the timed path
              keeps the model after one round

Leaves whose change in the reference is under a thousandth of the median
leaf's are left out of all three.
"""

from __future__ import annotations

import numpy as np


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}")
    else:
        yield prefix, np.asarray(tree, np.float64)


def leaf_gaps(prog_new, ref_new, init) -> tuple[float, float]:
    """(worst leaf's gap, median leaf's gap) of the change from ``init``, as
    the module's text says."""
    ref_norm, gap = {}, {}
    init = dict(_leaves(init))
    prog = dict(_leaves(prog_new))
    for name, leaf in _leaves(ref_new):
        ref_norm[name] = float(np.linalg.norm(leaf - init[name]))
        gap[name] = abs(float(np.linalg.norm(prog[name] - init[name]))
                        - ref_norm[name])
    med = float(np.median(list(ref_norm.values())))
    kept = [gap[name] / max(n, med) for name, n in ref_norm.items()
            if n >= 1e-3 * med]
    if not kept or not np.all(np.isfinite(kept)):
        return float("inf"), float("inf")
    return float(np.max(kept)), float(np.median(kept))


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared. ``prog`` and ``ref`` hold ``losses`` (a mean
    loss a round), ``init`` and ``models``: {rounds done: model}."""
    out = {}
    for i, lr in enumerate(ref["losses"]):
        lp = prog["losses"][i]
        g = abs(lp - lr) / abs(lr)
        out[f"loss_r{i}"] = g if np.isfinite(g) else float("inf")
    last = max(ref["models"])
    out["dparam"], out["dparam_med"] = leaf_gaps(
        prog["models"][last], ref["models"][last], ref["init"])
    if 1 in prog["models"] and 1 in ref["models"] and last != 1:
        out["grad1"], _ = leaf_gaps(prog["models"][1], ref["models"][1],
                                    ref["init"])
    return out


def decide(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}). Every number is compared:
    one whose limit the file lacks, or gives as ``null``, fails."""
    compared, ok = {}, True
    for name, value in nums.items():
        limit = limits["limits"].get(name)
        compared[name] = {"value": value,
                          "limit": "missing" if limit is None else limit}
        if limit is None or not value <= limit:
            ok = False
    return ok, compared
