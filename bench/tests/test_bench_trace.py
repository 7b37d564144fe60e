"""The reduction from trace to metrics, on a small synthetic trace."""
import re

import pytest

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


def _stepped():
    # device 0 runs two programs, [0,22) and [28,42), over the ops above
    tr = _trace()
    return T.Trace(tr.ops, tr.host, steps={0: [("jit_edge_step", 28, 42),
                                               ("jit_edge_step", 0, 22)]})


def test_idle_split_adds_up_to_the_idle_time():
    tr = _stepped()
    # idle [20,30) and [40,50): [20,22) [28,30) [40,42) inside a program
    assert tr.idle_split(0, 50) == (14, 6)
    assert tr.idle_split(0, 44) == (8, 6)
    assert tr.idle_split(25, 35) == (3, 2)
    # no program executions on device 1: all its idle time is between
    assert tr.idle_split(0, 50, device=1) == (10, 0)
    for t0, t1, dev in ((0, 50, 0), (0, 44, 0), (25, 35, 0), (3, 41, 0),
                        (0, 50, 1)):
        between, within = tr.idle_split(t0, t1, device=dev)
        assert between + within == (t1 - t0) - tr.busy_ns(t0, t1, dev)


def test_idle_gaps_inside_a_program_are_named_in_step():
    tr = _stepped()
    # [22,28) has its middle at 25, past the first batch's spans; [42,44)
    # at 43, inside the second batch's wait
    assert tr.idle_by_span(0, 44) == [(T.IN_STEP, 6), ("untracked", 6),
                                      ("edge_wait", 2)]
    named = dict(tr.idle_by_span(0, 50))
    assert named[T.IN_STEP] == tr.idle_split(0, 50)[1]
    assert sum(named.values()) == 50 - tr.busy_ns(0, 50, 0)


_XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000 }
    events { metadata_id: 1 offset_ps: 50000 duration_ps: 40000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000 duration_ps: 20000 }
    events { metadata_id: 3 offset_ps: 22000 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 51000 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_edge_step(7)" } }
  event_metadata { key: 2 value { id: 2
    name: "%custom-call.1 = f32[8,56]{1,0} custom-call(u8[8,56]{1,0} %p)"
    display_name: "custom-call.1"
    stats { metadata_id: 1 SCOPE } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8]{0} fusion()"
    stats { metadata_id: 2 int64_value: 5 } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "flops" } }
  stat_metadata { key: 3 value { id: 3
    name: "jit(edge_step)/jit(main)/dequantize/pallas_call" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 500 duration_ps: 300 }
    events { metadata_id: 3 offset_ps: 600 duration_ps: 100 }
    events { metadata_id: 4 offset_ps: 600 duration_ps: 150 } }
  event_metadata { key: 1 value { id: 1 name: "edge_window" } }
  event_metadata { key: 2 value { id: 2 name: "edge_call" } }
  event_metadata { key: 3 value { id: 3 name: "repro/edge_step" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(edge_step)" } }
}
"""


@pytest.mark.parametrize("scope_stat", [
    'str_value: "jit(edge_step)/jit(main)/dequantize/pallas_call"',
    "ref_value: 3"])
def test_from_profile_keeps_steps_scopes_and_program_spans(scope_stat):
    from jax.profiler import ProfileData
    xspace = ProfileData.text_proto_to_serialized_xspace(
        _XSPACE.replace("SCOPE", scope_stat))
    tr = T.Trace.from_profile(xspace, {"edge_window", "edge_call"})
    call = "%custom-call.1 = f32[8,56]{1,0} custom-call(u8[8,56]{1,0} %p)"
    assert tr.steps == {0: [("jit_edge_step(7)", 1000, 1040),
                            ("jit_edge_step(7)", 1050, 1090)]}
    assert tr.ops == {0: [(call, 1001, 1021),
                          ("%fusion.2 = f32[8]{0} fusion()", 1022, 1032),
                          (call, 1051, 1071)]}
    assert tr.scope_of == {
        call: "jit(edge_step)/jit(main)/dequantize/pallas_call"}
    # the unrelated host event is dropped
    assert [e[0] for e in tr.host] == ["edge_window", "edge_call",
                                       "repro/edge_step"]
    assert tr.idle_split(1000, 1100) == (20, 30)
