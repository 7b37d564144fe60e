"""The reduction from a profiler trace to the numbers the per-layer metrics
read: device busy time (the union of op intervals), the idle share, time
per device op, and the longest idle gaps named by what the host was doing.

A trace is read from the ``.xplane.pb`` file the JAX profiler writes,
through ``jax.profiler.ProfileData``. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per op run on
the chip. Host spans are the ``TraceAnnotation`` events that
``harness.Spans`` writes from the benchmark's own thread.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
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


class Trace:
    """``ops``: {device index: [(name, start_ns, end_ns), ...]} sorted by
    start; ``host``: [(name, start_ns, end_ns), ...] of the host spans."""

    def __init__(self, ops, host):
        self.ops = {d: sorted(evs, key=lambda e: e[1])
                    for d, evs in ops.items()}
        self.host = sorted(host, key=lambda e: e[1])

    @classmethod
    def from_profile(cls, pd, host_names):
        """Device ops and the host spans named in ``host_names`` from a
        ``ProfileData``."""
        ops, host = {}, []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
                elif not m and plane.name.startswith("/host"):
                    host.extend((e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events
                                if e.name in host_names)
        return cls(ops, host)

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

    def idle_by_span(self, t0, t1, device=0):
        """Idle nanoseconds of [t0, t1] on ``device`` by what the host was
        doing: each gap between ops goes to the innermost host span that
        covers its middle, or to "untracked". Returns [(name, ns), ...],
        most first."""
        busy = merge([(a, b) for _, a, b in self.ops.get(device, [])],
                     t0, t1)
        starts = [e[1] for e in self.host]
        out, prev = {}, t0
        for a, b in busy + [(t1, t1)]:
            if a > prev:
                name = self._span_at((prev + a) // 2, starts)
                out[name] = out.get(name, 0) + (a - prev)
            prev = max(prev, b)
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


def load(trace_dir, host_names):
    """The Trace in the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Trace.from_profile(ProfileData.from_file(max(
        files, key=os.path.getmtime)), set(host_names))
