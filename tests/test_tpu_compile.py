"""Compile the main-path Pallas kernels for a described TPU v5e chip.

This is the only test file that compiles for the chip. Nothing runs, so no
accelerator is needed, but the TPU compiler refuses here what interpret mode
accepts: an unsupported cast, a misaligned tile, too much VMEM. Each test
lowers one kernel through ``kernels.ops`` at a main-path width and asserts
that the program holds the Mosaic kernel (``tpu_custom_call``). The last
two compile the benchmark's edge step and check that its layers keep their
named scopes, and that BatchNorm's statistics fuse into the convolutions.

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
