"""The reduction from trace to metrics, on a small synthetic trace."""
import re

from bench import trace as T


def _trace():
    # device 0: ops at [0,10) [5,20) [30,40); device 1: [0,40)
    ops = {0: [("fusion.1", 0, 10), ("conv.2", 5, 20), ("dequant_kernel", 30, 40)],
           1: [("conv.2", 0, 40)]}
    host = [("edge_batch", 0, 25), ("edge_call", 0, 2), ("edge_wait", 2, 25),
            ("edge_batch", 26, 45), ("edge_call", 26, 31), ("edge_wait", 31, 45)]
    return T.Trace(ops, host)


def test_busy_union_and_idle_share():
    tr = _trace()
    assert T.union_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == 30
    assert tr.busy_ns(0, 50, device=0) == 30
    assert tr.busy_ns(0, 50, device=1) == 40
    assert tr.busy_ns(0, 50) == 35            # averaged over the chips
    assert tr.busy_ns(8, 35, device=0) == 17  # clipped to the window
    assert tr.span_window("edge_batch") == (0, 45)
    assert tr.span_window("missing") is None


def test_time_per_op():
    tr = _trace()
    assert tr.op_ns(0, 50) == {"fusion.1": 10, "conv.2": 15 + 40,
                               "dequant_kernel": 10}
    assert tr.op_ns(0, 50, match=re.compile("dequant")) == {
        "dequant_kernel": 10}
    assert tr.op_ns(35, 50, match=re.compile("dequant")) == {
        "dequant_kernel": 5}


def test_idle_time_named_by_the_innermost_host_span():
    tr = _trace()
    # device 0 is idle in [20,30) (middle 25: between the two batches) and
    # [40,50) (middle 45: past the last span)
    assert tr.idle_by_span(0, 50, device=0) == [("untracked", 20)]
    # [40,44) has its middle at 42, inside the second batch's wait
    assert tr.idle_by_span(0, 44, device=0) == [("untracked", 10),
                                                ("edge_wait", 4)]
    assert tr.idle_by_span(0, 50, device=1) == [("untracked", 10)]
    # the host spans cover [0,25) and [26,45); no ops at all
    empty = T.Trace({0: []}, tr.host)
    assert empty.idle_by_span(3, 30) == [("edge_wait", 27)]
