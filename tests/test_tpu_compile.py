"""Compile the main-path Pallas kernels for a described TPU v5e chip.

This is the only test file that compiles for the chip. Nothing runs, so no
accelerator is needed, but the TPU compiler refuses here what interpret mode
accepts: an unsupported cast, a misaligned tile, too much VMEM. Each test
lowers one kernel through ``kernels.ops`` at a main-path width and asserts
that the program holds the Mosaic kernel (``tpu_custom_call``). The last
ones compile the benchmark's edge steps and check that their layers keep
their named scopes, that BatchNorm's statistics fuse into the
convolutions, and that the routed experts run the Pallas grouped matmul.

The topology is described inside a fixture, never at import: one process at
a time may load the TPU library, and every test worker imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,e", [(1024, 2), (1024, 3), (300, 2), (300, 3)])
def test_pair_scorer_compiles(one_chip, n, e):
    S = lambda *shape: _spec(one_chip, shape)
    raw = {"d": S(n), "work": S(n), "active": S(n), "geom": S(e, 3),
           "consts": S(8)}
    srv_enc = {"w": S(4, 32), "b": S(32)}
    scorer = [{"w": S(163, 48), "b": S(48)}, {"w": S(48, 1), "b": S(1)}]
    _assert_mosaic(lambda u, r, se, sc: ops.pair_scorer(
        u, r, se, sc, impl="pallas", interpret=False),
        S(n, 128), raw, srv_enc, scorer)


def test_flat_trunk_compiles(one_chip):
    dims = (19, 64, 64, 13)
    qlayers = [{"codes": _spec(one_chip, (a, b), jnp.uint8),
                "mn": _spec(one_chip, ()), "mx": _spec(one_chip, ()),
                "b": _spec(one_chip, (b,))} for a, b in zip(dims, dims[1:])]
    _assert_mosaic(lambda x, q: ops.flat_trunk(x, q, impl="pallas",
                                               interpret=False),
                   _spec(one_chip, (10000, dims[0])), qlayers)


# the boundary after ResNet18's first module at 224x224, batch 8, with the
# autoencoder's 4x channel cut
BOUNDARY = (8, 16, 56, 56)


@pytest.mark.parametrize("op", ["quantize", "dequantize"])
def test_quant_compiles(one_chip, op):
    if op == "quantize":
        fn = lambda x, a, b: ops.quantize(x, a, b, impl="pallas",
                                          interpret=False)
        x = _spec(one_chip, BOUNDARY)
    else:
        fn = lambda y, a, b: ops.dequantize(y, a, b, impl="pallas",
                                            interpret=False)
        x = _spec(one_chip, BOUNDARY, jnp.uint8)
    _assert_mosaic(fn, x, _spec(one_chip, ()), _spec(one_chip, ()))


def test_bottleneck_encode_compiles(one_chip):
    _assert_mosaic(lambda x, w, a, b: ops.bottleneck_encode(
        x, w, a, b, interpret=False),
        _spec(one_chip, (4096, 2048)), _spec(one_chip, (2048, 512)),
        _spec(one_chip, ()), _spec(one_chip, ()))


@functools.cache
def _edge_step_hlo(arch, sharding):
    """(compiled HLO text, its computations {name: body lines}, the entry's
    body, model) of the benchmark's edge step at its cells' shapes (224x224,
    batch 32, codes at 1/16 of the channels after the first split point)."""
    from test_edge_scopes import edge_step

    fn, args, model, _ = edge_step(
        arch, impl="pallas", interpret=False, width=1.0, size=224,
        batch=32, classes=101, ratio=16, sharding=sharding)
    text = fn.lower(*args).compile().as_text()
    bodies, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            bodies[name.lstrip("%")] = []
        elif name is not None:
            bodies[name.lstrip("%")].append(line)
    entry = next(b for n, b in bodies.items() if f"ENTRY %{n} " in text)
    return text, bodies, entry, model


def _called(line):
    m = re.search(r"calls=%([\w.-]+)", line)
    return m and m.group(1)


@pytest.mark.parametrize("arch", ["resnet18", "mobilenetv2"])
def test_edge_step_layers_keep_their_scopes(one_chip, arch):
    """The benchmark's edge step at its cells' shapes: the dequantize
    kernel carries ``dequantize``, and every fusion that holds a
    convolution carries one program scope, a ``module<k>`` or the
    decode's ``ae_decode``, the modules after the split all among them."""
    from test_edge_scopes import scopes

    _, bodies, entry, model = _edge_step_hlo(arch, one_chip)
    with_conv = {n for n, b in bodies.items()
                 if any(" convolution(" in l for l in b)}

    def op_scopes(line):
        m = re.search(r'op_name="([^"]*)"', line)
        return scopes(m.group(1)) if m else []

    kernel = [op_scopes(l) for l in entry if "tpu_custom_call" in l]
    assert kernel == [["dequantize"]]
    conv = [op_scopes(l) for l in entry if _called(l) in with_conv]
    assert conv and all(len(s) == 1 for s in conv), conv
    start = model.split_after[0] + 1
    assert {f"module{i}" for i in range(start, model.n_modules)} <= {
        s[0] for s in conv}


# an activation of the edge step: batch 32, NCHW
ACTIVATION = re.compile(r"f32\[32,\d+,\d+,\d+\]\S* parameter\(")
VECTORS = re.compile(r"\(?f32\[\d+\]\{[^}]*\}(, f32\[\d+\]\{[^}]*\})*\)?")


@pytest.mark.parametrize("arch", ["resnet18", "mobilenetv2"])
def test_edge_step_bn_stats_fuse_into_producers(one_chip, arch):
    """BatchNorm's statistics take one pass: no fusion of the compiled edge
    step reads an activation only to return per-channel vectors, as the
    variance pass of the two-pass form ``mean((x - mean)^2)`` did (XLA
    names those ``multiply_reduce_fusion``). Both sums come out of the
    fusion that makes the activation."""
    text, bodies, entry, _ = _edge_step_hlo(arch, one_chip)
    reduce_only = []
    for line in entry:
        m = re.match(r"\s*%(\S+) = (.*?) fusion\(", line)
        if (m and VECTORS.fullmatch(m.group(2))
                and any(ACTIVATION.search(l) for l in bodies[_called(line)])):
            reduce_only.append(m.group(1))
    assert reduce_only == []
    assert "multiply_reduce_fusion" not in text


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_grouped_matmul_compiles(one_chip, k, n):
    """The routed experts' grouped matmul at the Moonlight cell's full
    shapes: 98,304 routed rows (16 x 1024 tokens x top 6), 64 experts, in
    ``gmm.tiling``'s tiles, whose blocks must fit the VMEM limit the
    kernel sets."""
    _assert_mosaic(lambda x, w, s: ops.grouped_matmul(
        x, w, s, impl="pallas", interpret=False),
        _spec(one_chip, (98304, k), jnp.bfloat16),
        _spec(one_chip, (64, k, n), jnp.bfloat16),
        _spec(one_chip, (64,), jnp.int32))


def test_dropless_train_step_compiles(one_chip, monkeypatch):
    """A training step of Moonlight at a small size (two layers, the
    second a dropless MoE layer, bfloat16 weights) with the Pallas
    grouped matmul: its forward is the Mosaic kernel, and its gradient
    compiles (``ragged_dot``'s VJP)."""
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.launch.steps import make_train_step
    from repro.models import init_params

    cfg = reduced(get_config("moonlight-16b-a3b"), d_model=256,
                  vocab=512).replace(param_dtype="bfloat16")
    assert cfg.moe.dropless
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    monkeypatch.setattr(ops, "_backend_impl", lambda: "pallas")
    train_step, opt_init = make_train_step(cfg)
    spec = lambda s: _spec(one_chip, s.shape, s.dtype)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(opt_init, params)
    tokens = _spec(one_chip, (2, 128), jnp.int32)
    _assert_mosaic(train_step, *jax.tree_util.tree_map(spec, (params, opt)),
                   {"tokens": tokens, "labels": tokens})


LLM_SCOPES = ("dequantize", "ae_decode", "mla", "moe_route", "moe_experts",
              "moe_shared", "lm_head")


def test_llm_edge_step_compiles(one_chip, monkeypatch):
    """The transformer edge step (``bench/entries/llm_edge.py``) at a small
    width: dequantize kernel, decode, two MLA + MoE layers with the routed
    experts' grouped matmul, the head. Every layer keeps its named scope,
    and the grouped matmul is the Pallas kernel, a Mosaic call under
    ``moe_experts``: no XLA ``ragged-dot`` is left."""
    import json
    import os

    from bench import harness
    from repro.core import compressor
    from repro.models import model

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    entry = harness.load_module(os.path.join(bench, "entries",
                                             "llm_edge.py"))
    ref = harness.load_module(os.path.join(bench, "reference",
                                           "moonlight.py"))
    with open(os.path.join(bench, "configs",
                           "moonlight16b-a3b-edge.json")) as f:
        c = json.load(f)
    c.update(hidden_size=256, num_attention_heads=4, kv_lora_rank=128,
             qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64,
             moe_intermediate_size=128, n_routed_experts=8,
             num_experts_per_tok=2, n_shared_experts=1, num_hidden_layers=3,
             vocab_size=1024)
    cfg = entry.program_config(c)
    # what the program chooses on the chip, where this process sees a CPU
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    monkeypatch.setattr(ops, "_backend_impl", lambda: "pallas")

    def step(params, dec, codes, mn, mx):
        z = ops.dequantize(codes, mn, mx, bits=8, impl="pallas")
        feat = compressor.decode({"dec": dec}, z)
        return model.edge_forward(entry.program_params(params), cfg, feat)

    edge = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda k: ref.init_edge(k, c), jax.random.PRNGKey(0)))
    text = jax.jit(step).lower(
        edge, _spec(one_chip, (64, 256)),
        _spec(one_chip, (2, 128, 64), jnp.uint8), _spec(one_chip, ()),
        _spec(one_chip, ())).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    found = {s for n in names for s in n.split(":")[0].split("/")}
    assert set(LLM_SCOPES) <= found
    assert not any("ragged-dot" in n for n in names)
    assert "ragged-dot" not in text
    mosaic = [re.search(r'op_name="([^"]*)"', l)
              for l in text.splitlines() if "tpu_custom_call" in l]
    experts = [m.group(1) for m in mosaic
               if m and "moe_experts" in m.group(1).split("/")]
    assert experts, [m and m.group(1) for m in mosaic]
