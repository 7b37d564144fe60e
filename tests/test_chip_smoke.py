"""``chip_smoke.py`` on the CPU at tiny sizes, so the script cannot rot
between chip runs: phases (b)-(d) in process (8 UEs, one iteration, 32x32
images), the four-chip phase on four virtual CPU devices, and the refusal
to report anything without a TPU. The lowering check (e) needs the chip's
compiler backend; ``test_tpu_compile.py`` covers its kernels."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trained(smoke):
    return smoke.train_phase(8, 1, 0)


def test_train_phase(trained):
    _, agent, hist = trained
    assert "entity_actor" in agent and len(hist) == 1
    assert np.isfinite(hist[0]["reward_mean"])


def test_dispatch_phase(smoke, trained):
    env, agent, _ = trained
    smoke.dispatch_phase(env, agent, rate=4.0, horizon=2.0, seed=0,
                         min_tasks=10)


def test_edge_phase(smoke):
    agreement = smoke.edge_phase(batch=8, size=32, ratio=4, bits=8, seed=0)
    assert sorted(agreement) == [1, 2, 3, 4]
    assert all(0.0 <= a <= 1.0 for a in agreement.values())


def test_check_device_refuses_cpu(smoke):
    with pytest.raises(SystemExit) as e:
        smoke.check_device(1)
    assert e.value.code != 0


def test_sharded_phase_on_four_virtual_devices():
    code = ("import chip_smoke as cs; cs.sharded_phase(n_ue=8, "
            "iterations=1, seed=0, n_shards=4, eval_frames=4)")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "shards [(0, " in out.stdout and "(3, " in out.stdout


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_without_tpu_or_repo(tmp_path, alone):
    """No verdict line and a non-zero exit on the CPU, and in a directory
    that holds the script and nothing else of the repo."""
    script = SCRIPT
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    out = subprocess.run([sys.executable, script], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env=_cpu_env(PYTHONPATH=""))
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
