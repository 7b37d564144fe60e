"""The harness: refusal off the chip or on an unknown chip, and cells,
configurations and metrics found by name without editing a file."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench import harness
from bench import run as bench_run
from bench_tiny import run, tiny_root

REPO = os.path.dirname(harness.BENCH_DIR)


def _fake_jax(platform, kind, count=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * count)


def test_unknown_device_kind_is_refused():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.check_device(_fake_jax("tpu", "TPU v9 imaginary"), 1)


def test_no_tpu_or_too_few_chips_is_refused():
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.check_device(_fake_jax("cpu", "cpu"), 1)
    with pytest.raises(harness.BenchError, match="needs 4 chips"):
        harness.check_device(_fake_jax("tpu", "TPU v5 lite", 1), 4)
    dev = harness.check_device(_fake_jax("tpu", "TPU v5 lite", 4), 4)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_command_exits_nonzero_off_the_chip(capsys):
    rc = bench_run.main(["--workload", "edge-resnet18-split1", "--seed",
                         "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_command_fails_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no system
    under test: the command exits non-zero and prints no result."""
    root = tiny_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "edge-resnet18-split1", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_dropped_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix, cell, limits file and metric
    reader are found from their names alone."""
    root = tiny_root(tmp_path)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "resnet18-fleet1024.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "resnet18-narrow-test"
    with open(os.path.join(bench, "configs", cfg["name"] + ".json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "edge-split1-b32.json")) as f:
        traffic = json.load(f)
    traffic["split"] = 2
    with open(os.path.join(bench, "traffic", "edge-split2-test.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "limits", "edge-new-test.json"), "w") as f:
        json.dump({"dequant_gap_steps": 1e-3, "logits_gap": 1e-3}, f)
    with open(os.path.join(bench, "metrics", "edge_batches_test.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return sum(1 for e in ctx.trace.host"
                " if e[0] == 'edge_call')\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "edge-new-test",
                              "config": cfg["name"],
                              "traffic": "edge-split2-test", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "edge_batches_test", "unit": "count",
                              "better": "higher", "source": "device_trace",
                              "layer": "edge model",
                              "moves": "edge_images_per_s",
                              "workloads": ["edge-new-test"]})
    with open(path, "w") as f:
        json.dump(spec, f)

    cell = harness.Cell("edge-new-test", root=root)
    assert cell.config["name"] == "resnet18-narrow-test"
    assert cell.traffic["split"] == 2
    assert [m["name"] for m in cell.per_layer()] == ["setup_compile_s",
                                                    "edge_batches_test"]
    result, _ = run(root, "edge-new-test", 5, trace=1)
    assert result["correct"]
    assert result["metrics"]["edge_batches_test"]["value"] > 0
