"""A reader of the profiler's ``.xplane.pb`` that needs no generated
protobuf code: the few fields of XSpace that the reduction reads, decoded
from the wire format by hand.

``jax.profiler.ProfileData`` gives events and their own stats, but not the
stats of an event's METADATA, and that is where XLA's TPU profiler puts an
op's ``hlo_category``. Field numbers are those of
``tsl/profiler/protobuf/xplane.proto``.
"""

from __future__ import annotations

import struct


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield no, wt, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """(name, value) of one XStat."""
    name, value = None, None
    for no, wt, v in fields(buf):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", v)[0]
        elif no in (3, 4):
            value = v
        elif no in (5, 6):
            value = _text(v)
        elif no == 7:
            value = stat_names.get(v, str(v))
    return name, value


def read_planes(path: str, want_plane=lambda name: True,
                want_line=lambda plane, line: True) -> list[dict]:
    """The planes of an XSpace file as dicts: ``name``, ``lines`` (each
    ``name`` and ``events`` as (metadata id, start s, end s)) and
    ``metadata``: {id: {"name", "stats"}} with the stats of the event's
    metadata."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for no, _, pbuf in fields(space):
        if no != 1:
            continue
        name, lines, meta_raw, stat_names = "", [], [], {}
        for pno, _, v in fields(pbuf):
            if pno == 2:
                name = _text(v)
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                meta_raw.append(v)
            elif pno == 5:
                for eno, _, ev in fields(v):
                    if eno == 2:
                        sid = sname = None
                        for sno, _, sv in fields(ev):
                            if sno == 1:
                                sid = sv
                            elif sno == 2:
                                sname = _text(sv)
                        stat_names[sid] = sname
        if not want_plane(name):
            continue
        metadata = {}
        for entry in meta_raw:
            for eno, _, ev in fields(entry):
                if eno != 2:
                    continue
                mid, mname, stats = None, "", {}
                for mno, _, mv in fields(ev):
                    if mno == 1:
                        mid = mv
                    elif mno == 2:
                        mname = _text(mv)
                    elif mno == 5:
                        k, val = _stat(mv, stat_names)
                        stats[k] = val
                metadata[mid] = {"name": mname, "stats": stats}
        out_lines = []
        for lbuf in lines:
            lname, t0_ns, events = "", 0, []
            raw_events = []
            for lno, _, v in fields(lbuf):
                if lno == 2:
                    lname = _text(v)
                elif lno == 3:
                    t0_ns = v
                elif lno == 4:
                    raw_events.append(v)
            if not want_line(name, lname):
                continue
            for ebuf in raw_events:
                mid = off = dur = 0
                for eno, _, v in fields(ebuf):
                    if eno == 1:
                        mid = v
                    elif eno == 2:
                        off = v
                    elif eno == 3:
                        dur = v
                start = t0_ns * 1e-9 + off * 1e-12
                events.append((mid, start, start + dur * 1e-12))
            out_lines.append({"name": lname, "events": events})
        planes.append({"name": name, "lines": out_lines,
                       "metadata": metadata})
    return planes
