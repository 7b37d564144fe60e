"""Public jit'd wrappers around the Pallas kernels.

A Pallas kernel compiles for the TPU and runs in interpret mode only on the
CPU backend (the tests). Wrappers handle shape normalization (flattening
leading dims, padding to block multiples where required).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import bottleneck as _bn
from repro.kernels import decode_attn as _da
from repro.kernels import gmm as _gmm
from repro.kernels import quant as _q


def _interpret_default():
    return jax.default_backend() == "cpu"


def _backend_impl():
    """The compiled Pallas kernel on TPU and the decomposed XLA form
    elsewhere (interpret-mode Pallas is for parity testing, not speed)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _impl_default(env_var):
    """The REPRO_*_IMPL convention shared by the older dual-impl ops: the
    env var wins, else ``_backend_impl()``."""
    return os.environ.get(env_var) or _backend_impl()


def quantize(x, mn, mx, *, bits=8, impl=None, interpret=None):
    """Any-shape fused quantization; returns integer codes of x.shape.

    ``impl``: "pallas" | "xla" | None (REPRO_QUANT_IMPL, else backend
    autodetection). Both impls share the exact elementwise math, so the
    codes are bitwise-identical; an explicit ``interpret`` implies the
    Pallas path."""
    if impl is None:
        impl = "pallas" if interpret is not None \
            else _impl_default("REPRO_QUANT_IMPL")
    if impl == "xla":
        return _q.quantize_xla(x, mn, mx, bits=bits)
    if impl != "pallas":
        raise ValueError(f"unknown quant impl {impl!r}")
    interpret = _interpret_default() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = _q.quantize_2d(x2, mn, mx, bits=bits, interpret=interpret)
    return out.reshape(shape)


def dequantize(y, mn, mx, *, bits=8, out_dtype=jnp.float32, impl=None,
               interpret=None):
    """Inverse of :func:`quantize`; same impl selection (REPRO_QUANT_IMPL).
    Either impl runs under the named scope ``dequantize``, so the device
    trace finds the same work whatever implements it."""
    if impl is None:
        impl = "pallas" if interpret is not None \
            else _impl_default("REPRO_QUANT_IMPL")
    with jax.named_scope("dequantize"):
        if impl == "xla":
            return _q.dequantize_xla(y, mn, mx, bits=bits,
                                     out_dtype=out_dtype)
        if impl != "pallas":
            raise ValueError(f"unknown quant impl {impl!r}")
        interpret = _interpret_default() if interpret is None else interpret
        shape = y.shape
        y2 = y.reshape(-1, shape[-1])
        out = _q.dequantize_2d(y2, mn, mx, bits=bits, out_dtype=out_dtype,
                               interpret=interpret)
        return out.reshape(shape)


def grouped_matmul(lhs, rhs, group_sizes, *, impl=None, interpret=None):
    """Rows of ``lhs`` (m, k), sorted into consecutive groups of
    ``group_sizes``, each times its group's ``rhs`` (g, k, n), with
    float32 accumulation: (m, n) float32.

    ``group_sizes`` is (g,) when the groups hold every row, or (g + 1,)
    when rows fall past them: the last count is those rows, and they come
    out zero. Only the second form pays for clearing them; with the first,
    rows past the groups are zero on the XLA path and undefined on the
    Pallas one.

    ``impl``: "pallas" | "xla" | None (``_backend_impl()``; an explicit
    ``interpret`` implies the Pallas path). "pallas" is the megablox
    grouped matmul at ``gmm.tiling``'s tiles, with ``ragged_dot``'s
    gradient (``kernels/gmm.py``); "xla" is ``jax.lax.ragged_dot``. On a
    v5e at the Moonlight cell's shapes (98,304 routed rows, 64 experts,
    the router's own group sizes) the Pallas kernel at (256, k, 512) took
    4.91 ms (2048 -> 1408) and 5.11 ms (1408 -> 2048), 59% and 56% of the
    bfloat16 peak, where ``ragged_dot`` took 12.88 and 9.34 ms. Of the
    tiles swept (tm 256-1024, tk k-512, tn n-128) those with the whole of
    k won: each expert's weights are fetched once for all its rows. The
    whole of n does not fit the kernel's 16 MiB of scoped VMEM. At tm 256
    the 63 group boundaries add a sixth to the MXU's work, at 512 a third;
    tm 512 took 5.11 and 4.94 ms, so the one rule gives up at most 0.17 ms
    a call."""
    if impl is None:
        impl = "pallas" if interpret is not None else _backend_impl()
    if impl == "xla":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes[:rhs.shape[0]],
                                  preferred_element_type=jnp.float32)
    if impl != "pallas":
        raise ValueError(f"unknown grouped_matmul impl {impl!r}")
    interpret = _interpret_default() if interpret is None else interpret
    return _gmm.gmm(lhs, rhs, group_sizes, None, interpret)


def bottleneck_encode(x, w, mn, mx, *, bits=8, interpret=None):
    """Fused compressor encode. x: (..., d); w: (d, d')."""
    interpret = _interpret_default() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = _bn.bottleneck_encode(x2, w, mn, mx, bits=bits, interpret=interpret)
    return out.reshape(shape[:-1] + (w.shape[1],))


def decode_attention(q, k, v, pos, idx, *, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _da.decode_attention(q, k, v, pos, idx, interpret=interpret)


def ssd_intra(xh, dt, la, Bm, Cm, *, interpret=None):
    """Mamba-2 SSD intra-chunk contribution (see kernels/ssd_intra.py)."""
    from repro.kernels import ssd_intra as _ssd
    interpret = _interpret_default() if interpret is None else interpret
    return _ssd.ssd_intra(xh, dt, la, Bm, Cm, interpret=interpret)


def flat_trunk(rows, qlayers, *, bits=8, impl=None, interpret=None):
    """Fused int8 dequant-matmul trunk forward -> (..., W) f32 head
    columns (see kernels/flat_trunk.py).

    ``rows``: (..., F) ``observe_per_ue``-style feature rows; ``qlayers``:
    the weight-quantized layer list from ``rl.distill.quantize_flat_trunk``
    ([{"codes", "mn", "mx", "b"}, ...] — biases stay f32). ``bits`` is
    static (pass it from the quantized trunk's bookkeeping, outside any
    jit trace). ``impl``: "pallas" | "xla" | None (REPRO_FLAT_TRUNK_IMPL,
    else the backend autodetection every dual-impl op uses)."""
    from repro.kernels import flat_trunk as _ft
    if impl is None:
        impl = _impl_default("REPRO_FLAT_TRUNK_IMPL")
    shape = rows.shape
    x2 = rows.reshape(-1, shape[-1])
    codes = tuple(l["codes"] for l in qlayers)
    mns = tuple(l["mn"] for l in qlayers)
    mxs = tuple(l["mx"] for l in qlayers)
    bs = tuple(l["b"] for l in qlayers)
    if impl == "xla":
        out = _ft.flat_trunk_xla(x2, codes, mns, mxs, bs, bits=bits)
    elif impl == "pallas":
        interpret = _interpret_default() if interpret is None else interpret
        out = _ft.flat_trunk_pallas(x2, codes, mns, mxs, bs, bits=bits,
                                    interpret=interpret)
    else:
        raise ValueError(f"unknown flat_trunk impl {impl!r}")
    return out.reshape(shape[:-1] + (out.shape[-1],))


def pair_scorer(ue_emb, raw, srv_enc, scorer, *, impl=None, interpret=None):
    """Fused entity route scorer -> (route_logits (N, E), srv_emb (E, S)).

    ``raw`` is the env's kernel-path observation block
    (``MECEnv.observe_entities_raw``: {"d", "work", "active", "geom",
    "consts"}); ``srv_enc``/``scorer`` are the matching subtrees of
    ``nets.init_entity_actor``. ``impl``: "pallas" | "xla" | None
    (autodetect: the Pallas kernel on TPU, the decomposed XLA form
    elsewhere — interpret-mode Pallas is for parity testing, not speed).
    Override with REPRO_PAIR_SCORER_IMPL."""
    from repro.kernels import pair_scorer as _ps
    if impl is None:
        impl = _impl_default("REPRO_PAIR_SCORER_IMPL")
    args = (ue_emb, raw["d"], raw["work"], raw["active"], raw["geom"],
            raw["consts"], srv_enc["w"], srv_enc["b"],
            scorer[0]["w"], scorer[0]["b"], scorer[1]["w"], scorer[1]["b"])
    if impl == "xla":
        return _ps.pair_scorer_xla(*args)
    if impl != "pallas":
        raise ValueError(f"unknown pair_scorer impl {impl!r}")
    interpret = _interpret_default() if interpret is None else interpret
    return _ps.pair_scorer_fused(interpret, *args)
