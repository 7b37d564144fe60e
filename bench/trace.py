"""The reduction from a profiler trace to the numbers the per-layer metrics
read: device busy time (the union of op intervals), the idle share and its
split into time between and within program executions, time per device
op, and the longest idle gaps named by what the host was doing.

A trace is read from the ``.xplane.pb`` file the JAX profiler writes,
through ``jax.profiler.ProfileData``. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per op run on
the chip, their ``XLA Modules`` line one event per program execution.
An op's scope path (the ``jax.named_scope``s around it, under the
``tf_op`` stat) sits on the op's event metadata, which ``ProfileData``
does not expose; ``op_scopes`` reads it from the serialized trace. Host
spans are the ``TraceAnnotation`` events that ``harness.Spans`` writes
from the benchmark's own thread, and those the program writes under the
``repro/`` prefix.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
STEPS_LINE = "XLA Modules"
SCOPE_STAT = "tf_op"
PROGRAM_SPAN_PREFIX = "repro/"
IN_STEP = "in_step"
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_KIND = re.compile(r"\s([a-z][\w-]*)\(")


def short_op_name(name):
    """``name (result type) kind`` from an op event's HLO text, with the
    layouts and operands left out: ``%fusion.7 = f32[8]{0} fusion(...)``
    becomes ``fusion.7 f32[8] fusion``."""
    if " = " not in name:
        return name
    lhs, rhs = _LAYOUT.sub("", name).split(" = ", 1)
    m = _OP_KIND.search(rhs)
    if m is None:
        return lhs.lstrip("%")
    return f"{lhs.lstrip('%')} {rhs[:m.start()].strip()} {m.group(1)}"


def _events(line, keep=None):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events if keep is None or keep(e.name)]


class Trace:
    """``ops``: {device index: [(name, start_ns, end_ns), ...]} sorted by
    start; ``host``: [(name, start_ns, end_ns), ...] of the host spans;
    ``steps``: {device index: [(program name, start_ns, end_ns), ...]}
    of the program executions, sorted; ``scope_of``: {op name: scope
    path} of the device ops that carry one."""

    def __init__(self, ops, host, steps=None, scope_of=None):
        self.ops = {d: sorted(evs, key=lambda e: e[1])
                    for d, evs in ops.items()}
        self.host = sorted(host, key=lambda e: e[1])
        self.steps = {d: sorted(evs, key=lambda e: e[1])
                      for d, evs in (steps or {}).items()}
        self.scope_of = dict(scope_of or {})

    @classmethod
    def from_profile(cls, xspace, host_names):
        """Device ops, program executions and op scopes, the host spans
        named in ``host_names`` and the program's own ``repro/`` spans,
        from a serialized XSpace (the bytes of an ``.xplane.pb``)."""
        from jax.profiler import ProfileData

        def keep(name):
            return name in host_names or name.startswith(PROGRAM_SPAN_PREFIX)

        ops, host, steps = {}, [], {}
        for plane in ProfileData.from_serialized_xspace(xspace).planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        _events(line))
                elif m and line.name == STEPS_LINE:
                    steps.setdefault(int(m.group(1)), []).extend(
                        _events(line))
                elif not m and plane.name.startswith("/host"):
                    host.extend(_events(line, keep))
        return cls(ops, host, steps, op_scopes(xspace))

    def span_window(self, name):
        """(first start, last end) of the host spans called ``name``, or
        None when the trace holds none."""
        evs = [e for e in self.host if e[0] == name]
        if not evs:
            return None
        return evs[0][1], max(e[2] for e in evs)

    def busy_ns(self, t0, t1, device=None):
        """Nanoseconds of [t0, t1] in which some op ran: on ``device``, or
        averaged over the devices in the trace."""
        devs = [device] if device is not None else sorted(self.ops)
        if not devs:
            return 0.0
        return sum(union_ns([(a, b) for _, a, b in self.ops.get(d, [])],
                            t0, t1) for d in devs) / len(devs)

    def op_ns(self, t0, t1, match=None):
        """{op name: nanoseconds inside [t0, t1]} summed over devices, for
        the ops whose name ``match`` (a compiled regex or None) finds."""
        out = {}
        for evs in self.ops.values():
            for name, a, b in evs:
                if match is not None and not match.search(name):
                    continue
                d = min(b, t1) - max(a, t0)
                if d > 0:
                    out[name] = out.get(name, 0) + d
        return out

    def idle_parts(self, t0, t1, device=0):
        """The idle time of [t0, t1] on ``device`` as [(start, end,
        in_step), ...]: each gap between ops, cut where a program
        execution begins or ends; ``in_step`` is True for a part inside
        one."""
        busy = merge([(a, b) for _, a, b in self.ops.get(device, [])],
                     t0, t1)
        steps = merge([(a, b) for _, a, b in self.steps.get(device, [])],
                      t0, t1)
        out, prev = [], t0
        for a, b in busy + [(t1, t1)]:
            if a > prev:
                out.extend(_cut(prev, a, steps))
            prev = max(prev, b)
        return out

    def idle_split(self, t0, t1, device=0):
        """(between_ns, within_ns): the idle time of [t0, t1] on
        ``device`` outside any program execution, and inside one with no
        op running. They add up to ``(t1 - t0) - busy_ns(t0, t1,
        device)``."""
        split = [0, 0]
        for a, b, in_step in self.idle_parts(t0, t1, device):
            split[in_step] += b - a
        return split[0], split[1]

    def idle_by_span(self, t0, t1, device=0):
        """Idle nanoseconds of [t0, t1] on ``device`` by where they fall:
        a part inside a program execution goes to "in_step"; each other
        part to the innermost host span that covers its middle, or to
        "untracked". Returns [(name, ns), ...], most first."""
        starts = [e[1] for e in self.host]
        out = {}
        for a, b, in_step in self.idle_parts(t0, t1, device):
            name = IN_STEP if in_step else self._span_at((a + b) // 2,
                                                         starts)
            out[name] = out.get(name, 0) + b - a
        return sorted(out.items(), key=lambda kv: -kv[1])

    def _span_at(self, t, starts, depth=8):
        """The innermost host span covering ``t``: of those that started
        last before it, as spans nest only a few deep."""
        i = bisect.bisect_right(starts, t)
        for e in reversed(self.host[max(0, i - depth):i]):
            if e[2] > t:
                return e[0]
        return "untracked"


def merge(intervals, t0, t1):
    """Intervals clipped to [t0, t1] and merged where they overlap."""
    clipped = sorted((max(a, t0), min(b, t1)) for a, b in intervals
                     if b > t0 and a < t1)
    out = []
    for a, b in clipped:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union_ns(intervals, t0, t1):
    """Length of the union of ``intervals`` inside [t0, t1]."""
    return sum(b - a for a, b in merge(intervals, t0, t1))


def _cut(a, b, steps):
    """[(start, end, in_step), ...] of the gap [a, b): its parts inside
    the merged, sorted ``steps`` and those between them."""
    i = max(bisect.bisect_right(steps, (a, a)) - 1, 0)
    out, t = [], a
    for s0, s1 in steps[i:]:
        if s0 >= b:
            break
        lo, hi = max(s0, t), min(s1, b)
        if hi <= lo:
            continue
        if lo > t:
            out.append((t, lo, False))
        out.append((lo, hi, True))
        t = hi
    if b > t:
        out.append((t, b, False))
    return out


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint or fixed-width field, a memoryview for a length-delimited
    one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        yield key >> 3, value


def _map_value(entry):
    """(key, value) of a protobuf map entry."""
    kv = dict(_fields(entry))
    return kv.get(1), kv.get(2)


def op_scopes(xspace):
    """{op name: scope path} from the device planes of a serialized
    XSpace: the ``tf_op`` stat of each op's event metadata, which holds
    the op-name metadata XLA keeps (``jit(f)/scope/.../primitive``).

    Fields of tsl's ``xplane.proto``: XSpace.planes 1; XPlane.name 2,
    .event_metadata 4, .stat_metadata 5; XEventMetadata.name 2, .stats 5;
    XStatMetadata.name 2; XStat.metadata_id 1, .str_value 5,
    .ref_value 7 (the id of a stat metadata holding the string)."""
    out = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        parts = {2: [], 4: [], 5: []}
        for f, v in _fields(plane):
            if f in parts:
                parts[f].append(v)
        if not any(DEVICE_PLANE.match(bytes(n).decode()) for n in parts[2]):
            continue
        stat_names = {}
        for entry in parts[5]:
            sid, meta = _map_value(entry)
            stat_names[sid] = bytes(dict(_fields(meta)).get(2, b"")).decode()
        for entry in parts[4]:
            _, meta = _map_value(entry)
            name, scope = None, None
            for f, v in _fields(meta):
                if f == 2:
                    name = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:
                        scope = stat_names.get(stat[7])
            if name and scope:
                out.setdefault(name, scope)
    return out


def load(trace_dir, host_names):
    """The Trace in the newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(max(files, key=os.path.getmtime), "rb") as f:
        return Trace.from_profile(f.read(), set(host_names))
