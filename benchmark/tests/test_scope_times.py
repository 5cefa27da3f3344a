"""The reduction by the program's own scopes and spans, on events worked
out by hand."""

import pytest

from benchmark import scope_times as st


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(block_fn)/jit(main)/while/body/closed_call/vmap(fed_gather)/"
     "jit(_take)/gather:", "fed_gather"),
    ("jit(round_fn)/transpose(jvp(fed_aggregate))/dot_general:",
     "fed_aggregate"),
    ("jit(block_fn)/while/body/closed_call/vmap()/while/body/Conv_0/"
     "conv_general_dilated:", "outside"),
    ("jit(block_fn)/while/body/fed_aggregate/dot_general:", "fed_aggregate"),
    ("jit(round_fn)/fed_server_update/fed_aggregate/add:",
     "fed_server_update"),
    ("jit(block_fn)/while/body/fed_gather/gather:", "fed_gather"),
    ("jit(block_fn)/while/body/copy:", "outside"),
    ("", "outside"),
])
def test_scope_of_matches_the_bare_token(tf_op, scope):
    assert st.scope_of(tf_op) == scope


def test_scope_of_reads_an_ops_long_name_too():
    hlo = '%fusion.3 = f32[8] fusion(...), metadata={op_name="a/fed_gather/b"}'
    assert st.scope_of(hlo) == "fed_gather"


def test_innermost_span_takes_each_piece():
    spans = [("bench:dispatch", 0.0, 10.0), ("fed:pack", 1.0, 6.0),
             ("fed:place", 4.0, 5.0), ("fed:round", 6.0, 9.0),
             ("fed:prefetch_pack", 8.5, 12.0)]   # another thread
    got = dict(st.innermost((0.5, 9.5), spans))
    assert got == pytest.approx({
        "bench:dispatch": 0.5,        # 0.5-1.0; 9.0-9.5 is the prefetch's
        "fed:pack": 4.0,              # 1-4 and 5-6
        "fed:place": 1.0,
        "fed:round": 2.5,             # 6-8.5
        "fed:prefetch_pack": 1.0})    # 8.5-9.5: it started last
    assert dict(st.innermost((20.0, 21.0), spans)) == {"host": 1.0}
    assert sum(t for _, t in st.innermost((0.5, 9.5), spans)) \
        == pytest.approx(9.0)


def test_reduce_events_by_hand():
    # window 10..20 from the harness's annotations
    fit, agg = "outside", "fed_aggregate"
    ops = {"/device:TPU:0": [
        ("outside", "body/while", "while", 10.0, 16.0),
        (fit, "Conv_0/conv_general_dilated", "convolution fusion",
         10.0, 12.0),
        (fit, "closed_call/transpose", "convolution fusion", 12.0, 13.0),
        (agg, "fed_aggregate/dot_general", "loop fusion", 14.0, 15.0),
        ("fed_server_update", "fed_server_update/add", "loop fusion",
         15.0, 15.5),
        ("outside", "while/copy", "data formatting", 18.0, 19.0),
        (fit, "early", "loop fusion", 8.0, 9.5),      # before the window
    ]}
    spans = [("bench:dispatch", 10.0, 10.5), ("bench:wait", 10.5, 17.0),
             ("bench:dispatch", 17.0, 18.2), ("fed:pack", 17.1, 17.9),
             ("fed:place", 17.5, 17.8), ("fed:round", 17.9, 18.1),
             ("bench:wait", 18.2, 20.0)]
    out = st.reduce_events(ops, spans)
    assert out["window_s"] == pytest.approx(10.0)
    chip = out["chips"]["/device:TPU:0"]
    assert chip["busy_s"] == pytest.approx(5.5)
    assert chip["leaf_s"] == pytest.approx(5.5)
    assert chip["scopes_s"] == pytest.approx({
        "fed_gather": 0.0, agg: 1.0, "fed_server_update": 0.5,
        "outside": 4.0})      # the local fit has no scope: it is `outside`
    assert chip["outside_ops"] == [
        ("Conv_0/conv_general_dilated", pytest.approx(2.0)),
        ("closed_call/transpose", pytest.approx(1.0)),
        ("while/copy", pytest.approx(1.0))]
    idle = out["idle_by_span"]
    # gaps 13-14, 15.5-18 and 19-20; 17-18 lies under the second dispatch
    assert idle["bench:wait"]["total_s"] == pytest.approx(3.5)
    assert idle["bench:wait"]["longest_s"] == pytest.approx(1.5)
    assert idle["bench:dispatch"]["total_s"] == pytest.approx(0.1)
    assert idle["fed:pack"]["total_s"] == pytest.approx(0.5)
    assert idle["fed:place"]["total_s"] == pytest.approx(0.3)
    assert idle["fed:round"]["total_s"] == pytest.approx(0.1)
    assert sum(v["total_s"] for v in idle.values()) == pytest.approx(
        out["window_s"] - chip["busy_s"])


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        st.reduce_events({"/device:TPU:0": []}, [])
