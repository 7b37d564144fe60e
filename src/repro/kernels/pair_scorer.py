"""Fused (UE, server) pair-scorer kernel for the entity route policy.

The entity policy's route head (``nets.entity_trunk``) scores every
(UE, server) pair with one shared MLP over ``[ue_embed ‖ server_embed ‖
edge_feats]``. The default XLA path materializes the (N, E, 3) edge
tensor inside ``MECEnv.observe_entities`` and the (N, E, 128+S+3) pair
concat inside the net — at N=1024 those intermediates dominate the
scorer's footprint. This kernel fuses the whole chain:

  * the per-(server, channel) interference/occupancy reduction
    ``per_slot = active.sum() / (E * C)`` (the one fleet-global scalar
    the server rows carry),
  * the server rows + single-layer server embedding,
  * the (N, E, 3) edge-feature build — pairwise distance, clean-channel
    rate proxy, and mean edge-service seconds — which never exists in
    memory: each (block_n, 1) column is produced and consumed in
    registers/VMEM,
  * the pair MLP, with the first layer DECOMPOSED by input block:
    ``tanh(ue @ W1u + srv_e @ W1s + edge_e @ W1e + b1)`` — the ue term
    is computed once per UE block instead of once per (UE, server) pair,

emitting (N, E) route logits and the (E, S) server embeddings directly.

All physics constants arrive through an 8-vector ``consts`` built by the
env (``MECEnv._scorer_consts``) so this module depends on nothing but
pallas:

  [pathloss, p_max, sigma_mean, omega_mean / RATE_NORM, t0,
   E * n_channels, DIST_NORM, 1 / EDGE_SLOW_NORM]

``pair_scorer_xla`` is the same decomposed computation expressed in
plain jnp — the fast path on CPU/GPU hosts (and the thing the bench
races against ``ref.pair_scorer_ref``'s naive materialized build). The
Pallas kernel runs compiled on TPU and in interpret mode on the CPU;
``pair_scorer_fused`` gives it the XLA form's gradient for training. Both
match ``kernels.ref.pair_scorer_ref`` to fp32 tolerance; ``active``
feeds ONLY the occupancy reduction (the default path scores inactive
rows too and masks at the action level), so churn parity is exact by
construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# consts-vector layout (see module docstring / MECEnv._scorer_consts)
C_PATHLOSS, C_PMAX, C_SIGMA, C_RATE_SCALE = 0, 1, 2, 3
C_T0, C_SLOT_DIV, C_DIST_NORM, C_SLOW_INV = 4, 5, 6, 7
N_CONSTS = 8


def _dot(a, b):
    """f32 matmul at full precision, so the compiled kernel meets the f32
    oracle as interpret mode does."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _edge_cols(d, work, g0, g1, g2, consts):
    """The three edge-feature columns for one server, from (bn, 1)
    distance/work columns and the server's geometry scalars. Mirrors
    ``observe_entities``' (N, E, 3) build column-by-column."""
    dist = d * g0
    gain = jnp.power(jnp.maximum(dist, 1.0), -consts[C_PATHLOSS])
    rate = g1 * consts[C_RATE_SCALE] \
        * jnp.log2(1.0 + consts[C_PMAX] * gain / consts[C_SIGMA])
    te = work * g2 / consts[C_T0]
    return dist / consts[C_DIST_NORM], rate, te


def _srv_row(g0, g1, g2, per_slot, consts):
    """One server's raw entity row [dist, bw, slowness/NORM, per_slot]."""
    return jnp.stack([g0, g1, g2 * consts[C_SLOW_INV],
                      per_slot]).reshape(1, 4)


def _scorer_kernel(consts_ref, geom_ref, act_ref, ue_ref, d_ref, work_ref,
                   wsrv_ref, bsrv_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                   logits_ref, srv_ref, *, n_srv, d_ue, s_dim):
    consts = consts_ref[0, :]
    # fused per-(server, channel) occupancy reduction over the FULL fleet
    per_slot = jnp.sum(act_ref[0, :]) / consts[C_SLOT_DIV]
    ue = ue_ref[...]                                    # (bn, d_ue)
    d = d_ref[...]                                      # (bn, 1)
    work = work_ref[...]                                # (bn, 1)
    w1 = w1_ref[...]                                    # (d_ue+S+3, 48)
    b1 = b1_ref[...]                                    # (1, 48)
    # the ue block of the decomposed first layer: once per block, not
    # once per (UE, server) pair
    ue_h = _dot(ue, w1[:d_ue, :])                      # (bn, 48)
    for e in range(n_srv):
        g0 = geom_ref[e, 0]
        g1 = geom_ref[e, 1]
        g2 = geom_ref[e, 2]
        semb = jnp.tanh(
            _dot(_srv_row(g0, g1, g2, per_slot, consts), wsrv_ref[...])
            + bsrv_ref[...])                            # (1, S)
        srv_ref[e, :] = semb[0]
        dist_c, rate_c, te_c = _edge_cols(d, work, g0, g1, g2, consts)
        edge = jnp.concatenate([dist_c, rate_c, te_c], axis=1)  # (bn, 3)
        h = jnp.tanh(
            ue_h
            + _dot(semb, w1[d_ue:d_ue + s_dim, :])
            + _dot(edge, w1[d_ue + s_dim:, :])
            + b1)                                       # (bn, 48)
        logit = _dot(h, w2_ref[...])
        logits_ref[:, e] = logit[:, 0] + b2_ref[0, 0]


def pair_scorer_pallas(ue_emb, d, work, active, geom, consts,
                       w_srv, b_srv, w1, b1, w2, b2, *,
                       block_n=256, interpret):
    """Fused pair scorer -> (route_logits (N, E), srv_emb (E, S)).

    ue_emb: (N, d_ue) tanh'd UE embeddings; d/work/active: (N,) raw
    per-UE vectors; geom: (E, 3) live pool geometry; consts: (8,) physics
    constants (layout above); the rest are the ``srv_enc``/``scorer``
    parameter arrays from ``nets.init_entity_actor``.
    """
    f32 = jnp.float32
    n, d_ue = ue_emb.shape
    n_srv = int(geom.shape[0])
    s_dim = int(w_srv.shape[1])
    bn = max(1, min(block_n, n))
    grid = (pl.cdiv(n, bn),)
    kernel = functools.partial(_scorer_kernel, n_srv=n_srv, d_ue=d_ue,
                               s_dim=s_dim)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    row = lambda width: pl.BlockSpec((bn, width), lambda i: (i, 0))
    logits, srv = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            full((1, N_CONSTS)),                        # consts
            full((n_srv, 3)),                           # geom
            full((1, n)),                               # active (full fleet)
            row(d_ue),                                  # ue_emb
            row(1),                                     # d
            row(1),                                     # work
            full((4, s_dim)),                           # w_srv
            full((1, s_dim)),                           # b_srv
            full((d_ue + s_dim + 3, w1.shape[1])),      # w1
            full((1, w1.shape[1])),                     # b1
            full((w2.shape[0], 1)),                     # w2
            full((1, 1)),                               # b2
        ],
        out_specs=(row(n_srv), full((n_srv, s_dim))),
        out_shape=(jax.ShapeDtypeStruct((n, n_srv), f32),
                   jax.ShapeDtypeStruct((n_srv, s_dim), f32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(consts.astype(f32).reshape(1, N_CONSTS), geom.astype(f32),
      active.astype(f32).reshape(1, n), ue_emb.astype(f32),
      d.astype(f32).reshape(n, 1), work.astype(f32).reshape(n, 1),
      w_srv.astype(f32), b_srv.astype(f32).reshape(1, s_dim),
      w1.astype(f32), b1.astype(f32).reshape(1, -1),
      w2.astype(f32), b2.astype(f32).reshape(1, 1))
    return logits, srv


def pair_scorer_xla(ue_emb, d, work, active, geom, consts,
                    w_srv, b_srv, w1, b1, w2, b2):
    """The decomposed pair scorer in plain jnp — same math as the Pallas
    kernel, vectorized over servers. Never materializes the (N, E,
    d_ue+S+3) pair concat the naive reference builds: the first scorer
    layer is split by input block so the dominant ue @ W1u product is
    (N, d_ue) @ (d_ue, 48) once, not per server."""
    f32 = jnp.float32
    ue_emb = ue_emb.astype(f32)
    d = d.astype(f32)
    work = work.astype(f32)
    active = active.astype(f32)
    geom = geom.astype(f32)
    consts = consts.astype(f32)
    d_ue = ue_emb.shape[1]
    s_dim = w_srv.shape[1]
    per_slot = active.sum() / consts[C_SLOT_DIV]
    srv_rows = jnp.concatenate([
        geom * jnp.stack([jnp.float32(1.0), jnp.float32(1.0),
                          consts[C_SLOW_INV]]),
        jnp.broadcast_to(per_slot, (geom.shape[0],))[:, None],
    ], axis=1)
    srv = jnp.tanh(srv_rows @ w_srv + b_srv)                   # (E, S)
    dist = d[:, None] * geom[None, :, 0]                       # (N, E)
    gain = jnp.power(jnp.maximum(dist, 1.0), -consts[C_PATHLOSS])
    rate = (geom[:, 1] * consts[C_RATE_SCALE])[None, :] \
        * jnp.log2(1.0 + consts[C_PMAX] * gain / consts[C_SIGMA])
    te = work[:, None] * geom[None, :, 2] / consts[C_T0]
    edge = jnp.stack([dist / consts[C_DIST_NORM], rate, te], axis=-1)
    h = jnp.tanh((ue_emb @ w1[:d_ue])[:, None, :]
                 + (srv @ w1[d_ue:d_ue + s_dim])[None, :, :]
                 + edge @ w1[d_ue + s_dim:]
                 + b1)                                         # (N, E, 48)
    logits = (h @ w2 + b2)[..., 0]                             # (N, E)
    return logits, srv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def pair_scorer_fused(interpret, *args):
    """``pair_scorer_pallas`` with a gradient: Mosaic kernels have no
    autodiff rule, so the backward pass differentiates
    ``pair_scorer_xla``, the same math. Training with the fused scorer
    (``MAHPPOConfig.fused_scorer``) takes its gradients through here."""
    return pair_scorer_pallas(*args, interpret=interpret)


def _fused_fwd(interpret, *args):
    return pair_scorer_pallas(*args, interpret=interpret), args


def _fused_bwd(interpret, args, cotangents):
    return jax.vjp(pair_scorer_xla, *args)[1](cotangents)


pair_scorer_fused.defvjp(_fused_fwd, _fused_bwd)
