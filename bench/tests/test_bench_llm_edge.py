"""The ``llm_edge`` entry end to end on the CPU at a tiny size: a run comes
out correct, its control and each planted fault do not."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench_tiny import run, tiny_root

CELL = "edge-moonlight16b-split1"
# Moonlight's architecture at a width the CPU runs in seconds: one dense
# layer on the UE, two MoE layers of 8 experts (top-2, one shared) here
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "num_hidden_layers": 3, "vocab_size": 512}


def tiny_llm_root(tmp):
    root = tiny_root(tmp)
    path = os.path.join(root, "bench", "configs", "moonlight16b-a3b-edge.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "bench", "traffic", "llm-split1-b16-t1024.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(batch=4, prompt_tokens=32)
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_llm_root(tmp_path_factory.mktemp("bench"))


def test_run_is_correct(root):
    result, checks = run(root, CELL, 2 ** 31 + 12345)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"edge_images_per_s", "setup_s"}
    assert set(checks) == {"dequant_gap_steps", "cache_gap", "logits_gap"}
    for c in checks.values():
        assert 0 <= c["value"] <= c["limit"]


def test_traced_run_reads_per_layer_metrics(root):
    result, _ = run(root, CELL, 7, trace=1)
    assert result["correct"]
    # the CPU has no device trace: only the counter reads
    assert set(result["metrics"]) == {"setup_compile_s"}
    assert result["device"]["window_s"] > 0


def test_control_is_not_correct(root, monkeypatch):
    """A whole run with the control, the reference in bfloat16 throughout,
    in the program's place fails a limit."""
    entry = harness.Cell(CELL, root=root).entry
    monkeypatch.setattr(entry.Edge, "_program_fn",
                        lambda self: self._reference_fn(entry.CONTROL))
    result, checks = run(root, CELL, 3, seconds=0.2)
    assert not result["correct"], checks
    assert result["failed"] > 0


def _expert_dropped(orig):
    """The first expert's rows come out of every grouped matmul as zeros:
    its tokens lose its share."""
    def grouped_matmul(lhs, rhs, group_sizes, **kw):
        out = orig(lhs, rhs, group_sizes, **kw)
        first = jnp.arange(out.shape[0]) < group_sizes[0]
        return jnp.where(first[:, None], 0.0, out)
    return grouped_matmul


def _norm_skipped(orig):
    """The latent's RMSNorm (``kv_a_layernorm``) left out: without a query
    low-rank path or a qk_norm, it is the only norm of attention."""
    return lambda scale, x, eps=1e-6: x


def _bias_ignored(orig):
    """Experts ranked by the sigmoid score alone."""
    def route(p, xf, m):
        return orig(dict(p, router_bias=jnp.zeros_like(p["router_bias"])),
                    xf, m)
    return route


def _faults():
    from repro.kernels import ops
    from repro.models import attention, moe
    return {"expert_dropped": (ops, "grouped_matmul", _expert_dropped),
            "kv_norm_skipped": (attention, "rms_head_norm", _norm_skipped),
            "bias_ignored": (moe, "route", _bias_ignored)}


@pytest.mark.parametrize("fault", ["expert_dropped", "kv_norm_skipped",
                                   "bias_ignored"])
def test_fault_is_caught(root, fault, monkeypatch):
    mod, name, wrap = _faults()[fault]
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    result, checks = run(root, CELL, 11, seconds=0.2)
    assert not result["correct"], checks
    assert result["failed"] > 0


SPLIT4 = "edge-resnet18-split4"


def test_split4_run_is_correct(root):
    """The head-only cell: codes after ResNet18's last stage, on the
    ``edge`` entry."""
    result, checks = run(root, SPLIT4, 2 ** 33 + 3)
    assert result["correct"], checks
    assert set(checks) == {"dequant_gap_steps", "logits_gap"}


def test_split4_control_is_not_correct(root, monkeypatch):
    entry = harness.Cell(SPLIT4, root=root).entry
    monkeypatch.setattr(entry.Edge, "_program_fn",
                        lambda self: self._reference_fn(entry.CONTROL))
    result, checks = run(root, SPLIT4, 3, seconds=0.2)
    assert not result["correct"], checks
