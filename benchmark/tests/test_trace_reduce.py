"""The reduction from trace events to busy time, idle share and gaps, on
events worked out by hand and on a small trace recorded on the chip."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_gaps_by_hand():
    ivals = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert tr.union_seconds(ivals) == pytest.approx(3.0)
    assert tr.gaps(ivals, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.gaps(ivals, 0.5, 3.5) == [(2.0, 3.0)]
    assert tr.union_seconds([]) == 0.0


def test_reduce_events_by_hand():
    # window 10..20 from the annotations; the loop event wraps its children
    ops = {"/device:TPU:0": [
        ("while.1", "while", 10.0, 16.0),
        ("fusion.1", "convolution fusion", 10.0, 12.0),
        ("fusion.2", "loop fusion", 12.0, 13.0),
        ("fusion.1", "convolution fusion", 14.0, 16.0),
        ("copy.1", "data formatting", 18.0, 19.0),
        ("early.1", "loop fusion", 8.0, 9.5),     # before the window
    ]}
    spans = [("dispatch", 10.0, 10.5), ("wait", 10.5, 17.0),
             ("dispatch", 17.0, 18.2), ("wait", 18.2, 20.0)]
    out = tr.reduce_events(ops, spans)
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_s"] == pytest.approx(6.0)       # 10-13, 14-16, 18-19
    assert out["conv_s"] == pytest.approx(4.0)
    top = dict((n, t) for n, t in out["breakdown"]["device_ops"])
    assert top["fusion.1 [convolution fusion]"] == pytest.approx(4.0)
    assert not any(n.startswith("while") for n in top)
    gaps = out["breakdown"]["idle_gaps"]
    # 13-14, 16-17 and 19-20 under wait; 17-18, of the gap 16-18, under
    # dispatch
    by = {g[0].split(" ")[0]: g for g in gaps}
    assert by["wait"][0].startswith("wait (longest of 3, total 3.0") and \
        by["wait"][1] == pytest.approx(1.0)
    assert by["dispatch"][0].startswith("dispatch (longest of 1, total 1.0")
    idle = 1.0 - out["busy_s"] / out["window_s"]
    assert idle == pytest.approx(0.4)


def test_no_annotations_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({"/device:TPU:0": []}, [])


def test_recorded_chip_trace():
    """A cut of a trace recorded on the v5e (PR 25, femnist_cnn_c256_block):
    the events of the window's first 40 ms and the harness's annotations,
    with the values the reduction gave when it was recorded."""
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        rec = json.load(f)
    ops = {k: [tuple(e) for e in v] for k, v in rec["device_ops"].items()}
    out = tr.reduce_events(ops, [tuple(s) for s in rec["host_spans"]])
    for key in ("window_s", "busy_s", "conv_s"):
        assert out[key] == pytest.approx(rec["expect"][key], rel=1e-9)
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert out["breakdown"]["device_ops"][0][0] == rec["expect"]["top_op"]
