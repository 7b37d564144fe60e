"""End-to-end system behaviour: training loop descends, checkpoint
round-trips, split tables are coherent, HLO analysis, optimizers, sharding
rules on a small host mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import INPUT_SHAPES, reduced
from repro.data.synthetic import TokenPipelineConfig, token_batch_stream
from repro.launch.steps import make_train_step
from repro.models import init_params


def _tiny_dense_cfg():
    return reduced(get_config("qwen3-1.7b"), n_layers=2, d_model=128,
                   vocab=256)


def test_train_loop_loss_decreases():
    cfg = _tiny_dense_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    train_step, opt_init = make_train_step(cfg, base_lr=3e-3, warmup=5,
                                           total=60)
    opt = opt_init(params)
    stream = token_batch_stream(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=32, batch=8))
    step = jax.jit(train_step)
    losses = []
    for i in range(40):
        batch = next(stream)
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses[::8]
    assert all(np.isfinite(losses))


def test_adafactor_descends():
    cfg = _tiny_dense_cfg().replace(optimizer="adafactor")
    params = init_params(cfg, jax.random.PRNGKey(0))
    train_step, opt_init = make_train_step(cfg, base_lr=3e-3, warmup=5,
                                           total=60)
    opt = opt_init(params)
    stream = token_batch_stream(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=32, batch=8))
    step = jax.jit(train_step)
    losses = []
    for _ in range(30):
        params, opt, metrics = step(params, opt, next(stream))
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


def test_checkpoint_roundtrip(tmp_path):
    from repro.ckpt import load_checkpoint, save_checkpoint
    cfg = _tiny_dense_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, params, step=7, extra={"arch": cfg.name})
    restored, meta = load_checkpoint(path, params)
    assert meta["step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_split_tables_all_archs():
    from repro.core.split import transformer_split_table
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        plan = transformer_split_table(cfg)
        n = plan.n_actions
        assert n == 6  # 4 points + raw-offload + full-local
        assert plan.t_local[0] == 0.0
        assert plan.f_bits[-1] == 0.0
        assert np.all(np.diff(plan.t_local[1:-1]) >= -1e-9), arch
        assert plan.feasible[0], arch  # raw offload always feasible
        if arch in ("kimi-k2-1t-a32b", "llama-3.2-vision-90b"):
            assert not plan.feasible[-1], f"{arch} can't run fully on a UE"


def test_hloanalysis_weighted_trip_counts():
    from repro.launch.hloanalysis import analyze

    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jnp.ones((128, 128))
    ws = jnp.ones((6, 128, 128))
    text = jax.jit(scanned).lower(x, ws).compile().as_text()
    res = analyze(text)
    assert res["hlo_dot_flops"] == pytest.approx(2 * 128**3 * 6, rel=1e-6)


def test_input_specs_cover_all_shapes():
    from repro.launch.steps import input_specs
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in INPUT_SHAPES:
            specs = input_specs(cfg, shape)
            leaves = jax.tree_util.tree_leaves(specs)
            assert leaves, (arch, shape)
            for leaf in leaves:
                assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_sharding_rules_small_mesh():
    """Param sharding specs build on a small host mesh and every spec
    divides its dim."""
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_host_mesh
    from repro.models import sharding as shd
    from repro.launch.steps import params_spec
    mesh = make_host_mesh(model_axis=1)
    cfg = get_config("qwen2-7b")
    pstruct = params_spec(cfg)
    shardings = shd.params_shardings(mesh, pstruct, cfg)
    for leaf, sh in zip(jax.tree_util.tree_leaves(pstruct),
                        jax.tree_util.tree_leaves(
                            shardings,
                            is_leaf=lambda x: isinstance(x, NamedSharding))):
        ss = sh.shard_shape(leaf.shape)  # raises if indivisible
        assert len(ss) == len(leaf.shape)


def test_dryrun_single_combo_host_mesh():
    """A reduced arch x shape lowers + compiles on the host mesh (the full
    512-device run lives in launch/dryrun.py artifacts)."""
    from repro.launch.mesh import make_host_mesh
    from repro.models import sharding as shd
    mesh = make_host_mesh(model_axis=1)
    cfg = _tiny_dense_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    train_step, opt_init = make_train_step(cfg)
    opt = opt_init(params)
    batch = {"tokens": jnp.ones((4, 16), jnp.int32),
             "labels": jnp.ones((4, 16), jnp.int32)}
    psh = shd.params_shardings(mesh, params, cfg)
    bsh = shd.batch_shardings(mesh, batch)
    fn = jax.jit(train_step, in_shardings=(psh, None, bsh))
    compiled = fn.lower(params, opt, batch).compile()
    ca = compiled.cost_analysis()
    assert ca["flops"] > 0


def test_data_pipeline_learnable_structure():
    """Markov stream has non-uniform transitions (cross-entropy of the true
    process is well below log(V))."""
    stream = token_batch_stream(TokenPipelineConfig(vocab_size=64, seq_len=64,
                                                    batch=4, n_modes=4))
    b = next(stream)
    assert b["tokens"].shape == (4, 64)
    # consecutive-token pairs repeat far more than uniform chance
    toks = np.asarray(b["tokens"]).reshape(-1)
    pairs = set(zip(toks[:-1], toks[1:]))
    assert len(pairs) < 0.5 * (len(toks) - 1)
