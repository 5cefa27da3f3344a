"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the window, the device's busy time, the time of its
convolution ops, the ops that took most time and the longest idle gaps by
what the host was doing. Reads the trace with ``xplane.py`` and nothing
else.

How a v5e trace is laid out (looked at by hand, PR 25): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event for each HLO op
that ran, with its ``hlo_category`` among the event's stats; ops inside a
``while`` nest under the loop's own event, which is why busy time is a union
of intervals and not a sum. An op's category is a stat of the event's METADATA, which
``jax.profiler.ProfileData`` does not show: ``xplane.py`` reads it. The
convolutions and the dense layers, forward and both gradients, are all
``convolution fusion``; the two ``lax.scan`` loops are ``while``. The host's
threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` shows there under its name.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench:"
# categories that wrap other ops: their own interval repeats their children's
CONTROL_CATEGORIES = ("while", "conditional", "call")
CONV_CATEGORIES = ("convolution", "convolution fusion")
TOP = 10


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals, in their unit."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The idle (start, end) gaps of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(gap, spans):
    """The pieces of ``gap`` by the host span that covers each:
    [(label, seconds)]; what no span covers goes to ``host``. Spans that
    follow one another do not overlap, so the pieces add up to the gap."""
    pieces, covered = [], 0.0
    for label, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > 0:
            pieces.append((label, cover))
            covered += cover
    rest = (gap[1] - gap[0]) - covered
    if rest > 1e-12:
        pieces.append(("host", rest))
    return pieces


def reduce_events(device_ops, host_spans) -> dict:
    """``device_ops``: {chip: [(name, category, start, end)]} in seconds on
    one clock; ``host_spans``: [(label, start, end)] of the harness's own
    annotations on the same clock. The window runs from the first
    annotation's start to the last one's end."""
    if not host_spans:
        raise ValueError("the trace holds none of the harness's annotations")
    lo = min(s for _, s, _ in host_spans)
    hi = max(e for _, _, e in host_spans)
    busy, conv, per_op, all_gaps = 0.0, 0.0, {}, []
    for ops in device_ops.values():
        leaf = [(n, c, max(s, lo), min(e, hi)) for n, c, s, e in ops
                if c not in CONTROL_CATEGORIES and min(e, hi) > max(s, lo)]
        ivals = [(s, e) for _, _, s, e in leaf]
        busy += union_seconds(ivals)
        conv += sum(e - s for _, c, s, e in leaf if c in CONV_CATEGORIES)
        for n, c, s, e in leaf:
            key = f"{n} [{c}]"
            per_op[key] = per_op.get(key, 0.0) + (e - s)
        all_gaps += gaps(ivals, lo, hi)
    by_host = {}
    for g in all_gaps:
        for label, piece in attribute(g, host_spans):
            by_host.setdefault(label, []).append(piece)
    used = max(len(device_ops), 1)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(((f"{label} (longest of {len(v)}, total "
                       f"{sum(v) / used:.6f}s)", max(v))
                      for label, v in by_host.items()),
                     key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": hi - lo,
        "busy_s": busy / used,
        "conv_s": conv / used,
        "breakdown": {
            "device_ops": [[n, t / used] for n, t in top_ops],
            "idle_gaps": [[n, t] for n, t in longest],
        },
    }


def short_name(hlo: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = f32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_label(meta: dict) -> str:
    """The tail of the jax op that an XLA op came from
    (``Conv_1/conv_general_dilated``), which survives a recompile that
    renumbers the fusions and groups the ops of one layer and direction;
    the op's own short name where the trace gives no jax op."""
    tail = str(meta["stats"].get("tf_op", "")).rstrip(":").split("/")[-2:]
    return "/".join(tail) if any(tail) else short_name(meta["name"])


def read_xplane(path: str):
    """(device_ops, host_spans) of one ``.xplane.pb`` file, in seconds."""
    from benchmark import xplane

    planes = xplane.read_planes(
        path, lambda n: n.startswith(DEVICE_PLANE) or n == HOST_PLANE,
        lambda plane, line: plane == HOST_PLANE or line == OPS_LINE)
    device_ops, host_spans = {}, []
    for plane in planes:
        meta = plane["metadata"]
        if plane["name"] == HOST_PLANE:
            labels = {mid: m["name"][len(ANNOTATION_PREFIX):]
                      for mid, m in meta.items()
                      if m["name"].startswith(ANNOTATION_PREFIX)}
            for line in plane["lines"]:
                host_spans += [(labels[mid], s, e)
                               for mid, s, e in line["events"]
                               if mid in labels]
            continue
        names = {mid: (op_label(m), str(m["stats"].get("hlo_category", "")))
                 for mid, m in meta.items()}
        ops = device_ops.setdefault(plane["name"], [])
        for line in plane["lines"]:
            ops += [(*names[mid], s, e) for mid, s, e in line["events"]]
    return device_ops, host_spans


def reduce_dir(tracedir: str) -> dict:
    """Reduce the one trace under ``tracedir`` (as ``jax.profiler.trace``
    leaves it)."""
    files = glob.glob(os.path.join(tracedir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {tracedir}, "
                           f"found {len(files)}")
    device_ops, host_spans = read_xplane(files[0])
    if not device_ops:
        raise RuntimeError("the trace holds no device plane: nothing ran on "
                           "a chip")
    return reduce_events(device_ops, host_spans)
