"""Expert-parallel shard_map MoE must agree with the pure-GSPMD global
dispatch. Runs in a subprocess with 8 forced host devices (the main test
process keeps 1 device — see conftest note)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, MoEConfig
from repro.models import meshctx
from repro.models.moe import (_apply_moe_global, apply_moe_ep,
                              apply_moe_ep_decode, init_moe)

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = ModelConfig(
    name="t", family="moe", n_layers=1, d_model=32, n_heads=2, n_kv_heads=1,
    d_ff=64, vocab_size=32, block_pattern=("moe",),
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=8.0,
                  n_shared_experts=1),
    param_dtype="float32", compute_dtype="float32", fsdp=True)
p = init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32)) * 0.5

ref, aux_ref = _apply_moe_global(p, x, cfg)
with meshctx.use_mesh(mesh):
    out_ep, aux_ep = jax.jit(lambda p, x: apply_moe_ep(p, x, cfg, mesh))(p, x)
    out_dec, aux_dec = jax.jit(
        lambda p, x: apply_moe_ep_decode(p, x, cfg, mesh))(p, x)

for name, out in (("ep", out_ep), ("ep_decode", out_dec)):
    err = float(jnp.max(jnp.abs(out - ref)))
    rel = err / (float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 2e-3, f"{name} mismatch rel={rel}"
    print(name, "ok", rel)
print("ALL_OK")
"""


@pytest.mark.slow
def test_ep_matches_global_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=os.getcwd(),
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert "ALL_OK" in res.stdout, res.stdout + "\n" + res.stderr


_DROPLESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.configs.base import reduced
from repro.models import meshctx
from repro.models.moe import apply_moe, apply_moe_dropless, init_moe

mesh = jax.make_mesh((2, 4), ("data", "model"))
# Kimi K2's layer at a small size: dropless noaux_tc router, 4 experts
# top-2, one shared expert; FSDP as the registered config has it
kimi = reduced(get_config("kimi-k2-1t-a32b"), d_model=32)
for fsdp in (True, False):
    cfg = kimi.replace(fsdp=fsdp)
    assert cfg.moe.dropless
    p = init_moe(jax.random.PRNGKey(0), cfg)
    p["router_bias"] = jax.random.normal(jax.random.PRNGKey(3), (4,)) * 0.1
    wspec = {"wi": P("model", "data" if fsdp else None, None),
             "wg": P("model", "data" if fsdp else None, None),
             "wo": P("model", None, "data" if fsdp else None)}
    shard = ({n: NamedSharding(mesh, wspec.get(n, P())) for n in p},
             NamedSharding(mesh, P("data")))
    # 32 tokens: the decode regime (under FSDP); 8192: per-shard dispatch
    for shape in ((4, 8, 32), (4, 2048, 32)):
        x = jax.random.normal(jax.random.PRNGKey(1), shape) * 0.5
        ref, aux_ref = apply_moe_dropless(p, x, cfg)
        with meshctx.use_mesh(mesh):
            f = jax.jit(lambda p, x: apply_moe(p, x, cfg),
                        in_shardings=shard)
            out, aux = f(p, x)
            hlo = f.lower(p, x).compile().as_text()
        rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 1e-5, (fsdp, shape, rel)
        assert abs(float(aux) - float(aux_ref)) < 1e-6 * float(aux_ref), (
            float(aux), float(aux_ref))
        if shape[1] == 2048:
            # experts sharded over 'model': no collective carries the
            # batch's 8192 tokens or their 16384 slots, only one data
            # shard's 4096 tokens
            rows = [int(r) for r in re.findall(
                r"= \w+\[(\d+)[,\]][^=]*? (?:all-gather|all-reduce|"
                r"all-to-all|collective-permute|reduce-scatter)\(", hlo)]
            assert rows and max(rows) <= 4096, (fsdp, sorted(rows))
        print(fsdp, shape, "ok", rel)
print("ALL_OK")
"""


def test_dropless_ep_matches_single_device_subprocess():
    """Kimi K2's dropless layer under an expert-parallel mesh gives the
    single-device answer, in the decode regime and the per-shard one,
    and keeps each data shard's tokens on it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", _DROPLESS_SCRIPT],
                         cwd=os.getcwd(), env=env, capture_output=True,
                         text=True, timeout=600)
    assert "ALL_OK" in res.stdout, res.stdout + "\n" + res.stderr
