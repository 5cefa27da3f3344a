"""The matrix work of a traced program, read off its own jaxpr.

:func:`matmul_work` walks a closed jaxpr and finds every ``dot_general`` and
``conv_general_dilated`` it issues, each as ``(scope, run, flops, bytes)``:

- ``flops`` is twice the multiply-adds of the shapes as issued: batch
  dimensions, ``feature_group_count`` and ``batch_group_count`` included, so
  a vmapped or width-packed convolution counts what runs, its zeros too.
  ``bytes`` is the operands and the result at their dtypes. It is the
  convention of ``benchmark/counts/*.matmul_ops_per_step``.
- ``scope`` is the innermost ``fed_*`` component of the equation's name
  stack, read from the program's top down, else ``outside``: the whole local
  fit lies under ``fed_client_fold``, the model's own scopes inside it.
- ``run`` says which pass the product belongs to:

  ``gradient``   the name stack holds ``transpose(``, or the equation lies in
                 the transposed part of a rematerialised region's backward
                 side: the products of the backward pass
  ``recompute``  the equation lies in the ``rematted_computation`` part of
                 such a region: the forward pass made again in the backward
                 pass, to save the memory of keeping its values
  ``forward``    everything else

  A rematerialised region (``jax.checkpoint``, ``nn.remat``) is on the
  backward side where its own equation is a gradient one. Its body then
  holds the region's forward pass under ``rematted_computation``, and the
  transposed products beside it with no ``transpose(`` of their own; a
  region nested in it has the same form again. ``tests/test_work.py``
  proves the rule on a toy with one region, and against the language
  models' own kept outputs.

A ``scan`` body counts ``length`` times. A ``cond`` counts by its LAST
branch, the one the program takes in the normal case: for the expert layer
(``lm_layers.expert_layer``) that is the budgeted tiles, which every step
takes while the assignments fit the row budget
(``fed_moe_fallback_steps_total`` counts the steps that took the full-size
branch instead). A ``while`` body counts once, its trip count unknown at
trace. The walk counts the jaxpr after jax's own dead-code elimination, as
the compiler would: a product whose result nothing reads is not counted (the
primal of a ``jax.vjp`` taken inside a custom backward pass, as
``ops/packed_conv.py`` takes one for its kernel gradient).

``core/program_store.py`` walks the round program it exports, and
``obs/perf_instrument.py`` keeps the counts (``fed_matmul_flops_total``,
``matmul_ops()``).
"""

from __future__ import annotations

import math
import re

from jax._src.interpreters.partial_eval import dce_jaxpr
from jax.extend.core import ClosedJaxpr, Jaxpr

RUNS = ("forward", "recompute", "gradient")
OUTSIDE = "outside"
_SCOPE = re.compile(r"fed_[A-Za-z0-9_]+")
# jax's rematerialisation primitive, under the names jax has given it
_REMAT = frozenset({"checkpoint", "remat", "remat2"})
_RECOMPUTED = "rematted_computation"


def _nbytes(aval) -> int:
    return math.prod(aval.shape) * aval.dtype.itemsize


def _dot_flops(eqn) -> int:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    ls, rs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    free_l = math.prod(d for i, d in enumerate(ls) if i not in lc + lb)
    free_r = math.prod(d for i, d in enumerate(rs) if i not in rc + rb)
    return 2 * math.prod(ls[i] for i in lb + lc) * free_l * free_r


def _conv_flops(eqn) -> int:
    """Each output element sums the kernel's spatial window over the
    kernel's input features (its share of the groups)."""
    rhs = eqn.invars[1].aval.shape
    spec = eqn.params["dimension_numbers"].rhs_spec  # (out, in, spatial...)
    window = math.prod(rhs[d] for d in spec[2:])
    return 2 * math.prod(eqn.outvars[0].aval.shape) * rhs[spec[1]] * window


_FLOPS = {"dot_general": _dot_flops, "conv_general_dilated": _conv_flops}


def _join(stack: str, own: str) -> str:
    return f"{stack}/{own}" if stack and own else stack or own


def _walk(jaxpr, stack: str, mode, rel: str, mult: float, out: dict):
    """``mode``: None at the program's own level (the name stack decides),
    ``"backward"`` inside the body of a rematerialised region on the
    backward side (``rel``, the stack within that body, decides),
    ``"recompute"`` inside a part made again."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        own = str(eqn.source_info.name_stack)
        full = _join(stack, own)
        if mode == "recompute":
            run = "recompute"
        elif mode == "backward":
            inner = _join(rel, own)
            run = "recompute" if _RECOMPUTED in inner else "gradient"
        else:
            run = "gradient" if "transpose(" in full else "forward"
        if name in _FLOPS:
            scopes = _SCOPE.findall(full)
            key = (scopes[-1] if scopes else OUTSIDE, run, _FLOPS[name](eqn),
                   sum(_nbytes(v.aval) for v in (*eqn.invars, *eqn.outvars)))
            out[key] = out.get(key, 0.0) + mult
            continue
        if run == "recompute":
            sub = ("recompute", "")
        elif name in _REMAT and run == "gradient":
            sub = ("backward", "")
        elif mode == "backward":
            sub = ("backward", _join(rel, own))
        else:
            sub = (None, "")
        if name == "cond":
            subs = [eqn.params["branches"][-1]]
        else:
            subs = [x for v in eqn.params.values()
                    for x in (v if isinstance(v, (tuple, list)) else (v,))
                    if isinstance(x, (ClosedJaxpr, Jaxpr))]
        m = mult * (eqn.params["length"] if name == "scan" else 1)
        for x in subs:
            _walk(x.jaxpr if isinstance(x, ClosedJaxpr) else x, full, *sub,
                  m, out)


def matmul_work(closed_jaxpr, rounds: int = 1) -> dict:
    """{(scope, run, flops, bytes): ops a round} of a traced program (the
    module text): each distinct product once, with how often it runs,
    divided by the ``rounds`` the program runs (its own scan over rounds)."""
    out: dict = {}
    jaxpr = closed_jaxpr.jaxpr
    jaxpr, _ = dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    _walk(jaxpr, "", None, "", 1.0, out)
    return {k: n / rounds for k, n in out.items()}
