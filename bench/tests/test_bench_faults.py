"""A whole run with the timed path broken underneath comes out not
correct, once for each fault an edge cell can have."""
import jax.numpy as jnp
import pytest

from bench_tiny import run, tiny_root
from repro.core import cnn
from repro.kernels import ops

CELLS = ("edge-resnet18-split1", "edge-mobilenetv2-split1")


def _code_altered(orig):
    """One dequantized value off by one code step where it is produced."""
    def dequantize(codes, mn, mx, *, bits=8, **kw):
        z = orig(codes, mn, mx, bits=bits, **kw)
        return z.at[(0,) * z.ndim].add((mx - mn) / ((1 << bits) - 1))
    return dequantize


def _logit_altered(orig):
    """The first image's answer changed: its class 0 logit set above the
    largest of the batch."""
    def forward_from(model, params, feat, start):
        out = orig(model, params, feat, start)
        return out.at[0, 0].set(jnp.max(out) + 0.1 * jnp.max(jnp.abs(out)))
    return forward_from


def _half_batch(orig):
    """The first half of the batch run and repeated: the batch's
    statistics are taken over the half alone."""
    def forward_from(model, params, feat, start):
        half = orig(model, params, feat[: feat.shape[0] // 2], start)
        return jnp.concatenate([half, half])
    return forward_from


FAULTS = {"code_altered": (ops, "dequantize", _code_altered),
          "logit_altered": (cnn, "forward_from", _logit_altered),
          "half_batch": (cnn, "forward_from", _half_batch)}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(root, workload, fault, monkeypatch):
    mod, name, wrap = FAULTS[fault]
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    result, checks = run(root, workload, 11, seconds=0.2)
    assert not result["correct"], checks
    assert result["failed"] > 0
