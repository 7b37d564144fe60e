"""Device op time by the transformer edge's layer scopes.

The program wraps the layers of ``models/`` in ``jax.named_scope``:
``mla`` (``models/attention.py``), ``moe_route``, ``moe_experts`` and
``moe_shared`` (``models/moe.py``) and ``lm_head`` (``models/model.py``).
``bench/scopes.py`` knows the CNN edge's names only; this module sums
``Trace.op_ns`` by these the same way: an op goes to the first segment of
its ``tf_op`` path that names one, once the ``:type`` suffix is dropped.

One exception: on the TPU, XLA expands ``jax.lax.ragged_dot`` into custom
calls it names ``ragged-dot-…`` and gives no scope (their ``tf_op`` is
that name, or missing). The routed experts' grouped matmul is the
program's only ragged dot, so those ops go to ``moe_experts``; an op
without a ``tf_op`` is judged by its own name.
"""
import re

SCOPE = re.compile(r"mla|moe_route|moe_experts|moe_shared|lm_head")
RAGGED_DOT = re.compile(r"ragged-dot")


def scope_of_path(path):
    """The layer scope of a ``tf_op`` path, or None."""
    if not path:
        return None
    if RAGGED_DOT.match(path):
        return "moe_experts"
    for segment in path.rsplit(":", 1)[0].split("/"):
        if SCOPE.fullmatch(segment):
            return segment
    return None


def op_ns(ctx, scope):
    """Op nanoseconds under ``scope`` inside the traced window."""
    return sum(ns for name, ns in ctx.trace.op_ns(*ctx.window).items()
               if scope_of_path(ctx.trace.scope_of.get(name) or name)
               == scope)


def ms_per_batch(ctx, scope):
    """Milliseconds of op time under ``scope`` per batch of the traced
    window, or None when no op of the window carries it."""
    batches = ctx.info["batches"]
    ns = op_ns(ctx, scope)
    if not batches or ns <= 0:
        return None
    return ns / 1e6 / batches
