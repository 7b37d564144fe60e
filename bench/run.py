"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the entry of ``BENCHMARK.json`` named by ``--workload``; its
files are found by name (see ``bench/harness.py``). A run sets the cell
up from the seed (weights, inputs, compilation, warm-up), measures for
``--seconds`` on the host clock, reads the peak device memory, frees the
program's state and compares a sample of what the window produced with
the plain reference. With ``--trace 0`` the result line carries the
cell's end-to-end metrics; with ``--trace 1`` a window of its own (at
most ``TRACE_SECONDS``) runs under the profiler and the line carries the
per-layer metrics, the device's busy and window seconds, and
``breakdown``.

The last line of standard output is the result as one JSON object. A run
that finds no TPU, fewer chips than the cell asks for, or a chip that
``bench/peaks.json`` does not list, exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402

TRACE_SECONDS = 3.0


class MetricContext:
    """What a per-layer metric reader gets: the cell, the entry's
    ``info()``, compile counters of the set-up, the host spans of the
    traced window, the reduced trace with its window, and the peaks."""

    def __init__(self, cell, info, setup, spans, trace, window, peaks):
        self.cell = cell
        self.info = info
        self.setup = setup
        self.spans = spans
        self.trace = trace
        self.window = window
        self.peaks = peaks
        self.window_s = (window[1] - window[0]) / 1e9
        self.busy_s = trace.busy_ns(*window) / 1e9

    def cost(self, name):
        return self.cell.cost(name)


def _breakdown(trace, window, k=10):
    from bench.trace import short_op_name
    ops = sorted(trace.op_ns(*window).items(), key=lambda kv: -kv[1])[:k]
    ops = [(short_op_name(n), ns) for n, ns in ops]
    top = trace.idle_by_span(*window)[:k]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in top]}


def run_cell(cell, seed, seconds, trace, *, jax, counter, device,
             t_start, devices):
    """Set up, measure, check. Returns (result dict, checks)."""
    work = cell.entry.build(cell, seed)
    setup = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    print(f"setup: setup_s={setup_s} " + " ".join(
        f"{k}={v}" for k, v in setup.items()), file=sys.stderr, flush=True)
    spans = harness.Spans(annotate=bool(trace))
    metrics = {}
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            # host spans come from TraceAnnotation; tracing every Python
            # call would slow the host the window measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                work.window(min(float(seconds), TRACE_SECONDS), spans)
            finally:
                jax.profiler.stop_trace()
            from bench import trace as trace_lib
            info = work.info()
            tr = trace_lib.load(tdir, cell.entry.SPAN_NAMES)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        window = tr.span_window(info["window_span"])
        if window is None:
            raise harness.BenchError("the trace holds no window span")
        if device["platform"] == "tpu":
            for line, found in ((trace_lib.OPS_LINE, tr.ops),
                                (trace_lib.STEPS_LINE, tr.steps)):
                if not found:
                    raise harness.BenchError(
                        f"the trace holds no {line!r} line on a device "
                        f"plane: the per-layer metrics would read nothing")
        peaks = cell.peaks(device["kind"])
        ctx = MetricContext(cell, info, setup, spans, tr, window, peaks)
        for m in cell.per_layer():
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=ctx.busy_s, window_s=ctx.window_s)
        breakdown = _breakdown(tr, window)
    else:
        e2e = work.window(float(seconds), spans)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        breakdown = None
    in_window = counter.compiles - setup["compiles"]
    if in_window:
        print(f"warning: {in_window} compiles inside the window",
              file=sys.stderr, flush=True)
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
        jax, devices))
    attempted = work.attempted()
    work.release()
    checks, failed = work.check()
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, checks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell(args.workload)
        import jax
        device = harness.check_device(jax, cell.chips)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    counter = harness.CompileCounter(jax)
    devices = jax.devices()[:cell.chips]
    result, checks = run_cell(cell, args.seed, args.seconds, args.trace,
                              jax=jax, counter=counter, device=device,
                              t_start=T_START, devices=devices)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
