"""Device time by the round program's own scopes, and idle gaps by the
program's own spans: a second reduction of the profiler's trace, kept beside
``trace_reduce.py`` (which ``run.py`` reads and this PR may not edit). Run by
hand through ``tests/chip_scopes.py``; a later ``benchmark`` PR wires it in.

The program names three steps with ``jax.named_scope``: ``fed_gather``,
``fed_aggregate``, ``fed_server_update``; the local fit is what is left
(``outside``, listed by op). A scope is a component of the jax name stack,
and XLA keeps the stack of the op an HLO op came from in the op's metadata.
On the v5e trace that is the stat ``tf_op`` of the event's metadata (looked
at by hand, PR 26: the whole stack, as in
``jit(block_fn)/while/body/closed_call/fed_gather/jit(_take)/gather:``), so
the tokens are looked for there first and in the op's long name (the HLO
text) after. A scope under a transform shows inside its wrapper
(``vmap(fed_x)``, ``transpose(jvp(fed_x))``), which is why the match is of
the bare token and not of a path component. A fusion has the stack of its
root op alone: device time goes to the scope of the root, whatever else XLA
fused in.

Host side: every ``RoundTracer`` span is the event ``fed:<name>`` of the
plane ``/host:CPU``, the harness's own annotations are ``bench:<name>``.
Each idle gap of the window is cut where a span starts or ends and each
piece goes to the innermost span that covers it: the one that started last.
"""

from __future__ import annotations

from benchmark import trace_reduce, xplane

SCOPES = ("fed_gather", "fed_aggregate", "fed_server_update")
OUTSIDE = "outside"
SPAN_PREFIXES = ("fed:", "bench:")
TOP = 10


def scope_of(text: str) -> str:
    """The scope an op's name stack puts it in: of the three tokens, the one
    that comes first in ``text`` (the outermost scope); ``outside`` where
    it holds none."""
    found = [(text.find(tok), tok) for tok in SCOPES if tok in text]
    return min(found)[1] if found else OUTSIDE


def innermost(gap, spans):
    """The pieces of ``gap`` by the innermost span that covers each:
    [(label, seconds)]; what no span covers goes to ``host``. ``spans``:
    [(label, start, end)], nested or on several threads."""
    lo, hi = gap
    cover = [(s, e, label) for label, s, e in spans if s < hi and e > lo]
    cuts = sorted({lo, hi, *(t for s, e, _ in cover for t in (s, e)
                             if lo < t < hi)})
    pieces = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [(s, -e, label) for s, e, label in cover if s <= a and e >= b]
        label = max(over)[2] if over else "host"
        pieces[label] = pieces.get(label, 0.0) + (b - a)
    return sorted(pieces.items())


def reduce_events(device_ops, host_spans) -> dict:
    """``device_ops``: {chip: [(scope, label, category, start, end)]} in
    seconds on one clock; ``host_spans``: [(label, start, end)] with the
    ``fed:``/``bench:`` prefix kept. The window is that of
    ``trace_reduce``: from the first ``bench:`` span's start to the last
    one's end, or of all spans where the harness put none."""
    window = [sp for sp in host_spans if sp[0].startswith("bench:")] \
        or host_spans
    if not window:
        raise ValueError("the trace holds no fed: or bench: span")
    lo = min(s for _, s, _ in window)
    hi = max(e for _, _, e in window)
    chips, idle = {}, {}
    for chip, ops in device_ops.items():
        leaf = [(sc, lb, max(s, lo), min(e, hi)) for sc, lb, c, s, e in ops
                if c not in trace_reduce.CONTROL_CATEGORIES
                and min(e, hi) > max(s, lo)]
        by_scope = dict.fromkeys(SCOPES + (OUTSIDE,), 0.0)
        outside = {}
        for sc, lb, s, e in leaf:
            by_scope[sc] += e - s
            if sc == OUTSIDE:
                outside[lb] = outside.get(lb, 0.0) + (e - s)
        ivals = [(s, e) for _, _, s, e in leaf]
        chips[chip] = {
            "busy_s": trace_reduce.union_seconds(ivals),
            "leaf_s": sum(by_scope.values()),
            "scopes_s": by_scope,
            "outside_ops": sorted(outside.items(),
                                  key=lambda kv: -kv[1])[:TOP],
        }
        for gap in trace_reduce.gaps(ivals, lo, hi):
            for label, piece in innermost(gap, host_spans):
                idle.setdefault(label, []).append(piece)
    used = max(len(device_ops), 1)
    return {
        "window_s": hi - lo,
        "chips": chips,
        "idle_by_span": {label: {"total_s": sum(v) / used,
                                 "longest_s": max(v), "pieces": len(v)}
                         for label, v in sorted(idle.items())},
    }


def read_xplane(path: str):
    """(device_ops, host_spans, matched_by) of one ``.xplane.pb`` file:
    ``matched_by`` counts the distinct ops whose scope was found in
    ``tf_op``, in the long name, or nowhere."""
    planes = xplane.read_planes(
        path,
        lambda n: n.startswith(trace_reduce.DEVICE_PLANE)
        or n == trace_reduce.HOST_PLANE,
        lambda plane, line: plane == trace_reduce.HOST_PLANE
        or line == trace_reduce.OPS_LINE)
    device_ops, host_spans = {}, []
    matched_by = {"tf_op": 0, "name": 0, OUTSIDE: 0}
    for plane in planes:
        meta = plane["metadata"]
        if plane["name"] == trace_reduce.HOST_PLANE:
            labels = {mid: m["name"] for mid, m in meta.items()
                      if m["name"].startswith(SPAN_PREFIXES)}
            for line in plane["lines"]:
                host_spans += [(labels[mid], s, e)
                               for mid, s, e in line["events"]
                               if mid in labels]
            continue
        names = {}
        for mid, m in meta.items():
            scope, source = scope_of(str(m["stats"].get("tf_op", ""))), "tf_op"
            if scope == OUTSIDE:
                scope, source = scope_of(m["name"]), "name"
            matched_by[OUTSIDE if scope == OUTSIDE else source] += 1
            names[mid] = (scope, trace_reduce.op_label(m),
                          str(m["stats"].get("hlo_category", "")))
        ops = device_ops.setdefault(plane["name"], [])
        for line in plane["lines"]:
            ops += [(*names[mid], s, e) for mid, s, e in line["events"]]
    return device_ops, host_spans, matched_by


def reduce_file(path: str) -> dict:
    """The two tables of one ``.xplane.pb`` file."""
    device_ops, host_spans, matched_by = read_xplane(path)
    if not device_ops:
        raise RuntimeError("the trace holds no device plane: nothing ran on "
                           "a chip")
    out = reduce_events(device_ops, host_spans)
    out["matched_by"] = matched_by
    return out
