"""A copy of the benchmark at a size the CPU runs in seconds: ResNet18 and
MobileNetV2 at their published widths on 64x64 images, batches of 2.
``run`` drives a whole run of a cell but skips the look for a chip."""
import json
import os
import shutil
import time

import jax

from bench import harness
from bench import run as bench_run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_AS_CHIP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _update(path, **kw):
    with open(path) as f:
        data = json.load(f)
    data.update(kw)
    with open(path, "w") as f:
        json.dump(data, f)


def tiny_root(tmp):
    """A checkout-like directory holding BENCHMARK.json and bench/, with
    every configuration at 64x64 and every edge traffic at batch 2."""
    root = str(tmp)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), root)
    for d, kw in (("configs", {"input_size": 64}),
                  ("traffic", {"batch": 2})):
        folder = os.path.join(root, "bench", d)
        for name in os.listdir(folder):
            _update(os.path.join(folder, name), **kw)
    return root


def run(root, workload, seed, *, seconds=0.5, trace=0):
    """(result line, checks) of one run of ``workload`` on the CPU."""
    t0 = time.perf_counter()
    cell = harness.Cell(workload, root=root)
    return bench_run.run_cell(
        cell, seed, seconds, trace, jax=jax,
        counter=harness.CompileCounter(jax), device=dict(CPU_AS_CHIP),
        t_start=t0, devices=jax.devices()[:1])
