"""Readings from which a cell's limits are set: the numbers its check
compares, over whole runs of the cell (``run.run_cell``), for the program
on many seeds and for the control on a few. The control is the reference
put in the program's place, computed one precision below the
configuration's (``edge.CONTROL``). Both run at the cell's own sizes and
load, in one process, so that the set-up compiles once.

  python3 bench/control.py --workload <name> --seeds 1 2 ... [--control 3]

One JSON line per run, then a summary line: the largest program reading
and the smallest control reading of each number. The benchmark's own runs
never run this.
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402


def readings(cell, seeds, n_control, seconds, **run_kw):
    """Yield {"seed", "side", "correct", name: value, ...}: a run of the
    program on every seed, then a run of the control on the first
    ``n_control``."""
    sides = [("program", s) for s in seeds]
    sides += [("control", s) for s in seeds[:n_control]]
    edge_cls = cell.entry.Edge
    program_fn = edge_cls._program_fn
    try:
        for side, seed in sides:
            if side == "control":
                edge_cls._program_fn = lambda self: self._reference_fn(
                    cell.entry.CONTROL)
            result, checks = bench_run.run_cell(
                cell, seed, seconds, 0, t_start=time.perf_counter(),
                **run_kw)
            yield dict({k: c["value"] for k, c in checks.items()},
                       seed=seed, side=side, correct=result["correct"])
    finally:
        edge_cls._program_fn = program_fn


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds, from the first, that also run the control")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell(args.workload)
        import jax
        device = harness.check_device(jax, cell.chips)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    run_kw = dict(jax=jax, counter=harness.CompileCounter(jax),
                  device=device, devices=jax.devices()[:cell.chips])
    top = {"program": {}, "control": {}}
    for r in readings(cell, args.seeds, args.control, args.seconds,
                      **run_kw):
        print(json.dumps(r), flush=True)
        side = top[r["side"]]
        pick = max if r["side"] == "program" else min
        for k, v in r.items():
            if k not in ("seed", "side", "correct"):
                side[k] = pick(side.get(k, v), v)
    print(json.dumps({"workload": args.workload, "program_max":
                      top["program"], "control_min": top["control"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
