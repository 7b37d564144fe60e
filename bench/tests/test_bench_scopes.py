"""Device time by program scope, and its readers, on a synthetic trace."""
import os
import types

import pytest

from bench import harness
from bench import scopes as S
from bench import trace as T

METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "metrics")
PER_BATCH = {"dequantize_ms_per_batch": "dequantize",
             "ae_decode_ms_per_batch": "ae_decode",
             "module2_ms_per_batch": "module2",
             "module3_ms_per_batch": "module3",
             "module4_ms_per_batch": "module4",
             "module5_ms_per_batch": "module5"}
READERS = sorted(PER_BATCH) + ["unscoped_share.edge"]


def _reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"))


@pytest.mark.parametrize("path,scope", [
    ("jit(edge_step)/module3/conv_general_dilated:", "module3"),
    ("jit(edge_step)/module12/add:Add", "module12"),
    ("jit(edge_step)/jit(main)/dequantize/pallas_call", "dequantize"),
    ("jit(edge_step)/ae_decode/bdhw,dc->bchw/dot_general:", "ae_decode"),
    ("jit(edge_step)/dequantize/jit(_where)/module2/select_n:", "dequantize"),
    ("jit(edge_step)/conv_general_dilated:", None),
    ("jit(edge_step)/modules/dequantize_xla/add:", None),
    ("jit(edge_step)/add:module2", None),
    ("", None),
    (None, None)])
def test_scope_of_a_tf_op_path(path, scope):
    assert S.scope_of_path(path) == scope


def _trace():
    # device 0, window [10, 100): a dequantize op, a decode op, two module
    # ops, a copy XLA added and an op with no tf_op stat at all
    ops = {0: [("%dq.1", 0, 20), ("%dot.2", 20, 25), ("%fusion.3", 25, 60),
               ("%copy-done.4", 60, 70), ("%fusion.5", 70, 95),
               ("%slice.6", 95, 110)]}
    scope_of = {
        "%dq.1": "jit(edge_step)/jit(main)/dequantize/pallas_call",
        "%dot.2": "jit(edge_step)/ae_decode/bdhw,dc->bchw/dot_general:",
        "%fusion.3": "jit(edge_step)/module2/conv_general_dilated:",
        "%copy-done.4": "jit(edge_step)/copy:",
        "%fusion.5": "jit(edge_step)/module4/conv_general_dilated:"}
    return T.Trace(ops, [], scope_of=scope_of)


def _ctx(trace, window=(10, 100), batches=2):
    return types.SimpleNamespace(trace=trace, window=window,
                                 info={"batches": batches})


def test_op_time_by_scope_is_clipped_and_adds_up():
    tr = _trace()
    by_scope = S.op_ns_by_scope(tr, (10, 100))
    assert by_scope == {"dequantize": 10, "ae_decode": 5, "module2": 35,
                        "module4": 25, None: 10 + 5}
    assert sum(by_scope.values()) == sum(tr.op_ns(10, 100).values())
    assert S.op_ns_by_scope(tr, (30, 65)) == {"module2": 30, None: 5}


def test_readers_divide_by_the_batches():
    ctx = _ctx(_trace())
    want = {"dequantize_ms_per_batch": 10 / 1e6 / 2,
            "ae_decode_ms_per_batch": 5 / 1e6 / 2,
            "module2_ms_per_batch": 35 / 1e6 / 2,
            "module3_ms_per_batch": None,
            "module4_ms_per_batch": 25 / 1e6 / 2,
            "module5_ms_per_batch": None,
            "unscoped_share.edge": 100.0 * 15 / 90}
    got = {name: _reader(name).read(ctx) for name in READERS}
    assert got == pytest.approx(want)
    assert _reader("module2_ms_per_batch").read(
        _ctx(_trace(), batches=7)) == pytest.approx(35 / 1e6 / 7)


@pytest.mark.parametrize("trace", [
    T.Trace({}, []),
    T.Trace({0: []}, []),
    T.Trace({0: [("%fusion.1", 0, 50)]}, []),
    T.Trace({0: [("%fusion.1", 0, 50)]}, [],
            scope_of={"%fusion.1": "jit(edge_step)/conv_general_dilated:"})],
    ids=["no-ops", "no-device-ops", "no-tf_op", "no-program-scope"])
@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_their_scope(trace, name):
    assert _reader(name).read(_ctx(trace)) is None


@pytest.mark.parametrize("name", sorted(PER_BATCH))
def test_a_reader_reads_nothing_when_only_other_scopes_are_there(name):
    others = {s for s in PER_BATCH.values() if s != PER_BATCH[name]}
    ops = {0: [(f"%op.{i}", 10 * i, 10 * i + 5)
               for i, _ in enumerate(sorted(others))]}
    scope_of = {f"%op.{i}": f"jit(edge_step)/{s}/add:"
                for i, s in enumerate(sorted(others))}
    assert _reader(name).read(_ctx(T.Trace(ops, [], scope_of=scope_of),
                                   window=(0, 100))) is None
