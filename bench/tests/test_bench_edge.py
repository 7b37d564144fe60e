"""The ``edge`` entry end to end on the CPU at a tiny size: a run comes
out correct, its control does not."""
import pytest

from bench import harness
from bench_tiny import run, tiny_root

CELLS = ("edge-resnet18-split1", "edge-mobilenetv2-split1")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", CELLS)
def test_run_is_correct(root, workload):
    result, checks = run(root, workload, 2 ** 31 + 12345)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"edge_images_per_s", "setup_s"}
    assert set(checks) == {"dequant_gap_steps", "logits_gap"}
    for c in checks.values():
        assert 0 <= c["value"] <= c["limit"]


def test_traced_run_reads_per_layer_metrics(root):
    result, _ = run(root, CELLS[0], 7, trace=1)
    assert result["correct"]
    # the CPU has no device trace: only the counter and the spans read
    assert set(result["metrics"]) == {"setup_compile_s", "edge_call_ms_p50"}
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(root, workload, monkeypatch):
    """A whole run with the control, the reference in bfloat16, in the
    program's place fails a limit."""
    entry = harness.Cell(workload, root=root).entry
    monkeypatch.setattr(entry.Edge, "_program_fn",
                        lambda self: self._reference_fn(entry.CONTROL))
    result, checks = run(root, workload, 3, seconds=0.2)
    assert not result["correct"], checks
    assert result["failed"] > 0


@pytest.mark.parametrize("line", ["XLA Ops", "XLA Modules"])
def test_tpu_trace_without_a_device_line_is_an_error(root, line,
                                                      monkeypatch):
    """On a TPU, a trace that lacks a device plane's ``XLA Ops`` or
    ``XLA Modules`` line stops the run, rather than leaving metrics out."""
    import jax

    from bench import run as bench_run
    from bench import trace as trace_lib
    from bench_tiny import CPU_AS_CHIP

    real_load = trace_lib.load

    def load(trace_dir, host_names):
        host = real_load(trace_dir, host_names).host
        ops = {} if line == trace_lib.OPS_LINE else {0: [("op", 0, 1)]}
        steps = {} if line == trace_lib.STEPS_LINE else {0: [("s", 0, 1)]}
        return trace_lib.Trace(ops, host, steps)

    monkeypatch.setattr(trace_lib, "load", load)
    cell = harness.Cell(CELLS[0], root=root)
    with pytest.raises(harness.BenchError, match=line):
        bench_run.run_cell(
            cell, 5, 0.3, 1, jax=jax, counter=harness.CompileCounter(jax),
            device=dict(CPU_AS_CHIP, platform="tpu"), t_start=0.0,
            devices=jax.devices()[:1])
