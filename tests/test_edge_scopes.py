"""The edge half names its layers in the compiled program.

``ops.dequantize`` runs under the named scope ``dequantize`` whichever
impl implements it, ``compressor.decode`` under ``ae_decode``, and module
``k`` of ``cnn.forward_from`` under ``module<k>``. A scope reaches each
op's ``op_name`` metadata, which the device trace reports as the op's
``tf_op`` and the benchmark reads as the op's layer. The programs here
are the benchmark's edge step (dequantize, decode, then the modules after
the first split point) at a small width and size, compiled for the CPU.
"""
import collections
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import cnn, compressor
from repro.kernels import ops

PROGRAM_SCOPE = re.compile(r"dequantize|ae_decode|module\d+")
OP_NAME = re.compile(r'op_name="([^"]*)"')
ARCHS = ("resnet18", "mobilenetv2")


def scopes(op_name):
    """The program scopes on an op's path, outermost first:
    ``jit(f)/module3/conv_general_dilated`` gives ``["module3"]``."""
    return [s for s in op_name.split(":")[0].split("/")
            if PROGRAM_SCOPE.fullmatch(s)]


def op_names(hlo_text, keep=lambda line: True):
    """The ``op_name`` of each instruction of a compiled HLO text that
    ``keep`` accepts; instructions without one give ``""``."""
    out = []
    for line in hlo_text.splitlines():
        if " = " in line and keep(line):
            m = OP_NAME.search(line)
            out.append(m.group(1) if m else "")
    return out


def edge_step(arch, *, impl, interpret=None, width=0.25, size=64, batch=2,
              classes=11, ratio=4, sharding=None):
    """(jitted edge step, argument shapes, model, the function that
    rebuilds the parameter tree from its arrays) for ``arch`` split after
    its first split point, with codes at ``1/ratio`` of the channels."""
    model = cnn.CNN_FACTORY[arch](classes, width=width)
    start = model.split_after[0] + 1
    skeleton = {}

    def init_arrays(key):
        leaves, treedef = jax.tree_util.tree_flatten(model.init(key))
        skeleton["treedef"] = treedef
        skeleton["static"] = [None if hasattr(x, "shape") else x
                              for x in leaves]
        return [x for x in leaves if hasattr(x, "shape")]

    def rebuild(arrays):
        it = iter(arrays)
        return jax.tree_util.tree_unflatten(
            skeleton["treedef"],
            [next(it) if s is None else s for s in skeleton["static"]])

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    arrays = [spec(a.shape, a.dtype) for a in
              jax.eval_shape(init_arrays, jax.random.PRNGKey(0))]
    c, h, w = model.feature_shapes(size)[start - 1]
    d = max(1, c // ratio)

    def step(arrays, dec, codes, mn, mx):
        z = ops.dequantize(codes, mn, mx, bits=8, impl=impl,
                           interpret=interpret)
        feat = compressor.decode({"dec": dec}, z)
        return z, cnn.forward_from(model, rebuild(arrays), feat, start)

    args = (arrays, spec((d, c)), spec((batch, d, h, w), jnp.uint8),
            spec(()), spec(()))
    return jax.jit(step), args, model, rebuild


def _compiled(fn, args):
    return fn.lower(*args).compile().as_text()


def _convs_issued(model, rebuild, arrays, i, x):
    """The convolutions module ``i`` issues, traced on its own."""
    jaxpr = jax.make_jaxpr(
        lambda a, x: model.run_module(rebuild(a)[i], i, x))(arrays, x)
    return sum(e.primitive.name == "conv_general_dilated"
               for e in jaxpr.eqns)


@pytest.mark.parametrize("arch", ARCHS)
def test_each_convolution_carries_the_module_that_issued_it(arch):
    """Before optimization each convolution carries one ``module<k>``, as
    many per module as the module issues alone. XLA's CPU compiler turns
    some into dots and transposes; each op it makes of a convolution
    keeps one ``module<k>``."""
    fn, args, model, rebuild = edge_step(arch, impl="xla")
    start, size, batch = model.split_after[0] + 1, 64, 2
    shapes = model.feature_shapes(size)
    want = collections.Counter()
    for i in range(start, model.n_modules):
        x = jax.ShapeDtypeStruct((batch,) + tuple(shapes[i - 1]),
                                 jnp.float32)
        n = _convs_issued(model, rebuild, args[0], i, x)
        if n:
            want[f"module{i}"] = n
    assert sum(want.values()) > 0
    lowered = fn.lower(*args).as_text(dialect="hlo", debug_info=True)
    found = collections.Counter()
    for name in op_names(lowered, lambda l: " convolution(" in l):
        assert len(scopes(name)) == 1, name
        found[scopes(name)[0]] += 1
    assert found == want
    compiled = [n for n in op_names(_compiled(fn, args))
                if n.endswith("/conv_general_dilated")]
    assert all(len(scopes(n)) == 1 for n in compiled), compiled
    assert {scopes(n)[0] for n in compiled} == set(want)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_dequantize_runs_under_its_scope_in_either_impl(impl):
    """Every op that the dequantize of the codes alone compiles to carries
    the scope, and in the edge step the scope is there and alone on each
    op."""
    interpret = True if impl == "pallas" else None
    codes = jax.ShapeDtypeStruct((2, 16, 16, 16), jnp.uint8)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)
    alone = jax.jit(lambda y, a, b: ops.dequantize(
        y, a, b, impl=impl, interpret=interpret))
    # a parameter, and a bitcast of one, is named after the argument
    named = [n for n in op_names(_compiled(alone, (codes, f32, f32)))
             if n.startswith("jit(")]
    assert named and all(scopes(n) == ["dequantize"] for n in named), named
    fn, args, _, _ = edge_step("resnet18", impl=impl, interpret=interpret)
    names = op_names(_compiled(fn, args))
    assert any(scopes(n) == ["dequantize"] for n in names)
    assert all(len(scopes(n)) <= 1 for n in names)


def test_resnet18_decode_carries_ae_decode():
    fn, args, _, _ = edge_step("resnet18", impl="xla")
    names = op_names(_compiled(fn, args))
    decode = [n for n in names if scopes(n) == ["ae_decode"]]
    assert decode and all(n.endswith("dot_general") for n in decode)
