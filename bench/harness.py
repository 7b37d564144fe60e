"""What every cell of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, the device check, the peaks table, compile
counting, host spans, and the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Its files:

* ``bench/configs/<config>.json``  the configuration's sizes;
* ``bench/traffic/<traffic>.json`` the traffic parameters, including the
  entry that drives them;
* ``bench/entries/<entry>.py``     the path the window drives;
* ``bench/limits/<workload>.json`` the limit of each number compared;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric;
* ``bench/costs/<name>.py``        operations and bytes from shapes;
* ``bench/peaks.json``             chip peaks keyed by ``device_kind``.

Nothing here imports the program: entries do.
"""
from __future__ import annotations

import importlib.util
import json
import logging
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """A cell that cannot run here: no chip, an unknown chip, a missing
    file. The command exits non-zero and prints no result."""


def load_module(path):
    """Import a Python file by path, once per path. Metric names hold
    dots, so metric readers are loaded this way rather than by package
    import."""
    path = os.path.abspath(path)
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {path}")
    name = "bench_dyn_" + path.replace(os.sep, "_").replace(
        ".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def _read_json(path):
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with its files, found by name
    under ``root``."""

    def __init__(self, workload, root=ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.spec = _read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                             f"have {sorted(cells)}")
        self.name = workload
        self.entry_spec = cells[workload]
        self.chips = int(self.entry_spec["chips"])
        self.config = self.data("configs", self.entry_spec["config"])
        self.traffic = self.data("traffic", self.entry_spec["traffic"])
        self.limits = self.data("limits", workload)
        self.entry = load_module(os.path.join(
            self.bench_dir, "entries", self.traffic["entry"] + ".py"))

    def data(self, kind, name):
        return _read_json(os.path.join(self.bench_dir, kind, name + ".json"))

    def end_to_end(self):
        """This cell's end-to-end metrics: those with no ``workloads`` key
        and those that list it."""
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """This cell's per-layer metrics: those that list it, and those
        without a ``workloads`` key whose ``moves`` metric it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    def metric_reader(self, name):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        name + ".py"))

    def cost(self, name):
        return load_module(os.path.join(self.bench_dir, "costs",
                                        name + ".py"))

    def peaks(self, device_kind):
        return peaks_for(device_kind, self.bench_dir)


def peaks_for(device_kind, bench_dir=BENCH_DIR):
    """The peaks row of ``device_kind``. A kind not in the table is an
    error, never a default."""
    table = _read_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def check_device(jax, chips, bench_dir=BENCH_DIR):
    """The device JAX reports, as the result line names it. Raises
    BenchError unless there are ``chips`` TPUs of a kind in the peaks
    table."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX's first device is on "
                         f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{dev['count']}")
    peaks_for(dev["kind"], bench_dir)
    return dev


CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def enable_compile_cache(jax):
    """JAX's persistent compilation cache at ``.jax_cache`` in the
    checkout, a fixed path whatever the environment names, so that only
    a checkout's first run of a cell compiles and two checkouts share
    nothing. Every program of the cell is cached, however quick."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter(logging.Filter):
    """Seconds in XLA compilation (or loading from the persistent cache)
    and persistent-cache hits, from JAX's monitoring events, and the names
    of the programs that missed the persistent cache, from the compiler's
    debug log (which is read here and not printed). Tracing is left out:
    a nested jit reports its trace inside its caller's."""

    MISS = "PERSISTENT COMPILATION CACHE MISS"

    def __init__(self, jax):
        super().__init__()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.missed = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(min(log.getEffectiveLevel(), logging.DEBUG))
        log.addFilter(self)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def filter(self, record):
        if isinstance(record.msg, str) and record.msg.startswith(self.MISS):
            self.missed.append(str(record.args[0]))
        return record.levelno > logging.DEBUG

    def snapshot(self):
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": len(self.missed),
                "missed": ",".join(self.missed)}


class Spans:
    """Host spans the harness records around its calls into the program:
    name -> list of (start_ns, end_ns) on ``time.perf_counter_ns``. With
    ``annotate`` each span is also written into the profiler's trace
    (``jax.profiler.TraceAnnotation``), on the device trace's clock."""

    def __init__(self, annotate=False):
        self.spans = {}
        self._annotate = annotate
        if annotate:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def span(self, name):
        return _Span(self, name)

    def add(self, name, t0, t1):
        self.spans.setdefault(name, []).append((t0, t1))

    def durations_ms(self, name):
        return [(b - a) / 1e6 for a, b in self.spans.get(name, [])]


class _Span:
    __slots__ = ("owner", "name", "t0", "ann")

    def __init__(self, owner, name):
        self.owner, self.name = owner, name
        self.ann = owner._ann(name) if owner._annotate else None

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.owner.add(self.name, self.t0, t1)
        return False


def memory_peak_bytes(jax, devices):
    """Peak bytes in use on the fullest of ``devices``, or None where the
    backend does not report it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def emit(result, checks):
    """Print the compared numbers as the last lines of standard error,
    then the result line, with the checks under the key that comes last,
    as the last line of standard output."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
