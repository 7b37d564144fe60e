"""Device op time by the program's own layer names.

The program wraps its layers in ``jax.named_scope``: ``dequantize``
(``kernels/ops.py``), ``ae_decode`` (``core/compressor.py``) and
``module<k>`` for module ``k`` of the CNN (``core/cnn.py``). The scope
path reaches the ``tf_op`` stat of each device op, which
``Trace.scope_of`` holds by op name. Ops that carry none of these scopes
are XLA's own: copies between memory spaces, slices, relayouts.
"""
import re

SCOPE = re.compile(r"dequantize|ae_decode|module\d+")


def scope_of_path(path):
    """The program scope of a ``tf_op`` path, or None: the first segment
    that names one, once the ``:type`` suffix is dropped.
    ``jit(edge_step)/module3/conv_general_dilated:`` gives ``module3``."""
    if not path:
        return None
    for segment in path.rsplit(":", 1)[0].split("/"):
        if SCOPE.fullmatch(segment):
            return segment
    return None


def op_ns_by_scope(trace, window):
    """{scope or None: op nanoseconds inside ``window``}, summed over the
    ops of ``trace.op_ns``; ops with no program scope go to None."""
    out = {}
    for name, ns in trace.op_ns(*window).items():
        scope = scope_of_path(trace.scope_of.get(name))
        out[scope] = out.get(scope, 0) + ns
    return out


def ms_per_batch(ctx, scope):
    """Milliseconds of op time under ``scope`` per batch of the traced
    window, or None when no op of the window carries it."""
    batches = ctx.info["batches"]
    ns = op_ns_by_scope(ctx.trace, ctx.window).get(scope, 0)
    if not batches or ns <= 0:
        return None
    return ns / 1e6 / batches
