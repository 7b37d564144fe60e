"""Model assembly: embedding -> leading dense layers -> scanned block
groups (+tail) -> head.

The layer stack is compiled as lax.scan over the groups of
``block_pattern`` with per-pattern-position stacked parameters, so HLO
size (and compile time) is independent of depth. The config's
``first_dense_layers`` run unstacked before the groups as the "lead";
layers that don't fill a whole group run unstacked as the "tail".

``split_params`` cuts the parameters at a layer for the paper's UE/edge
split; ``ue_forward`` and ``edge_forward`` run the two halves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import meshctx
from repro.models.blocks import apply_block, init_block
from repro.models.layers import (apply_norm, dense_init, dtype_of, embed_init,
                                 init_norm, mm)


def layer_plan(cfg):
    """(lead block types, pattern, number of whole pattern groups, tail
    block types)."""
    lead = ("dense",) * cfg.first_dense_layers
    pat = tuple(cfg.block_pattern)
    n_rest = cfg.n_layers - len(lead)
    n_groups = n_rest // len(pat)
    tail = tuple(pat[i % len(pat)] for i in range(n_groups * len(pat),
                                                  n_rest))
    return lead, pat, n_groups, tail


# --------------------------------------------------------------------- init
def _init_stack(key, cfg, pattern, n_groups, tail_types, lead_types=()):
    keys = jax.random.split(key, len(pattern) + max(len(tail_types), 1))
    blocks = []
    for j, bt in enumerate(pattern):
        gkeys = jax.random.split(keys[j], n_groups)
        blocks.append(jax.vmap(lambda k, b=bt: init_block(k, cfg, b))(gkeys))
    tail = [init_block(keys[len(pattern) + i], cfg, bt)
            for i, bt in enumerate(tail_types)]
    lkeys = jax.random.split(jax.random.fold_in(key, 1), len(lead_types))
    lead = [init_block(k, cfg, bt) for k, bt in zip(lkeys, lead_types)]
    return {"lead": tuple(lead), "blocks": tuple(blocks),
            "tail": tuple(tail), "ln_f": init_norm(cfg)}


def init_params(cfg, key):
    k_emb, k_stack, k_head, k_enc = jax.random.split(key, 4)
    lead_types, pattern, n_groups, tail_types = layer_plan(cfg)
    dt = dtype_of(cfg.param_dtype)
    params = {
        "embed": embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dt),
        "decoder": _init_stack(k_stack, cfg, pattern, n_groups, tail_types,
                               lead_types),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)
    if cfg.family == "encdec":
        enc = cfg.encoder
        params["encoder"] = _init_stack(
            k_enc, cfg, ("enc",), enc.n_layers, ())
    return params


# ------------------------------------------------------------------- stack
def _run_stack(stack, x, cfg, pattern, tail_types, *, positions, mode,
               context=None, cache=None, idx=None, attn_len=0,
               final_norm=True):
    """Runs the stack's lead layers (dense blocks), its scanned groups and
    its tail. Returns (x, new_cache_or_None, aux_loss_sum)."""
    n_groups = None
    for leaf in jax.tree_util.tree_leaves(stack["blocks"]):
        n_groups = leaf.shape[0]
        break

    seq_par = cfg.seq_parallel_residual and mode in ("train", "prefill")

    def group_body(x, pgroup, cgroup):
        entries, aux_tot = [], 0.0
        for j, bt in enumerate(pattern):
            # pin the residual stream to batch/data sharding: stops GSPMD
            # flipping to batch-replicated layouts around FSDP weights
            x = meshctx.wsc_batch(x, seq_parallel=seq_par)
            x, ce, aux = apply_block(
                pgroup[j], x, cfg, bt, positions=positions, mode=mode,
                context=context, cache=None if cgroup is None else cgroup[j],
                idx=idx, attn_len=attn_len)
            entries.append(ce)
            aux_tot = aux_tot + aux
        x = meshctx.wsc_batch(x)
        return x, tuple(entries), jnp.asarray(aux_tot, jnp.float32)

    if mode == "train" and cfg.remat:
        group_body = jax.checkpoint(group_body)

    new_cache = {"lead": (), "blocks": (), "tail": ()}
    aux_total = jnp.zeros((), jnp.float32)

    lead_entries = []
    for i, p_i in enumerate(stack["lead"]):
        x, ce, aux = apply_block(
            p_i, x, cfg, "dense", positions=positions, mode=mode,
            context=context, cache=None if cache is None else
            cache["lead"][i], idx=idx, attn_len=attn_len)
        lead_entries.append(ce)
        aux_total = aux_total + aux
    new_cache["lead"] = tuple(lead_entries)

    if n_groups:
        if cache is None:
            def body(x, pgroup):
                x, entries, aux = group_body(x, pgroup, None)
                return x, (entries, aux)
            x, (entries, auxs) = jax.lax.scan(body, x, stack["blocks"])
        else:
            def body(x, xs):
                pgroup, cgroup = xs
                x, entries, aux = group_body(x, pgroup, cgroup)
                return x, (entries, aux)
            x, (entries, auxs) = jax.lax.scan(
                body, x, (stack["blocks"], cache["blocks"]))
        new_cache["blocks"] = entries
        aux_total = aux_total + auxs.sum()

    tail_entries = []
    for i, bt in enumerate(tail_types):
        ce_in = None if cache is None else cache["tail"][i]
        x, ce, aux = apply_block(
            stack["tail"][i], x, cfg, bt, positions=positions, mode=mode,
            context=context, cache=ce_in, idx=idx, attn_len=attn_len)
        tail_entries.append(ce)
        aux_total = aux_total + aux
    new_cache["tail"] = tuple(tail_entries)

    if final_norm:
        x = apply_norm(stack["ln_f"], x, cfg)
    if mode == "train":
        new_cache = None
    return x, new_cache, aux_total


def _logits(params, cfg, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    with jax.named_scope("lm_head"):
        if x.dtype != head.dtype:
            return mm(x, head)
        return x @ head


def apply_model(params, cfg, tokens, *, positions=None, aux_embeds=None,
                mode="train", cache=None, idx=None, attn_len=0):
    """tokens: (B, S) int32. aux_embeds: (B, n_aux, d_model) stubbed modality
    frontend output (audio frames / image patches). Returns
    (logits, new_cache, aux_loss)."""
    b, s = tokens.shape
    if positions is None:
        if mode == "decode":
            positions = jnp.full((b, s), idx, jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        dtype_of(cfg.compute_dtype))

    context = None
    if cfg.family == "encdec" and mode != "decode":
        enc_pos = jnp.broadcast_to(
            jnp.arange(aux_embeds.shape[1], dtype=jnp.int32),
            aux_embeds.shape[:2])
        ctx, _, _ = _run_stack(
            params["encoder"], aux_embeds.astype(x.dtype), cfg, ("enc",), (),
            positions=enc_pos, mode="train")
        context = ctx
    elif cfg.family == "vlm" and mode != "decode":
        context = None if aux_embeds is None else aux_embeds.astype(x.dtype)

    _, pattern, _, tail_types = layer_plan(cfg)
    x, new_cache, aux = _run_stack(
        params["decoder"], x, cfg, pattern, tail_types, positions=positions,
        mode=mode, context=context, cache=cache, idx=idx, attn_len=attn_len)
    logits = _logits(params, cfg, x)
    return logits, new_cache, aux


# ------------------------------------------------------------- UE / edge
def _cut_stack(stack, cfg, lo, hi):
    """Layers [lo, hi) of a decoder stack in the stack's own layout. A cut
    may fall between lead layers or between pattern groups, not inside a
    group or the tail."""
    lead, pattern, n_groups, tail = layer_plan(cfg)
    nl, pl = len(lead), len(pattern)
    end = nl + n_groups * pl
    for b in (lo, hi):
        if not (b <= nl or b == cfg.n_layers
                or (b <= end and (b - nl) % pl == 0)):
            raise ValueError(f"a cut at layer {b} falls inside a pattern "
                             f"group or the tail of {cfg.name}")
    g0, g1 = ((min(max(b, nl), end) - nl) // pl for b in (lo, hi))
    return {"lead": tuple(stack["lead"][lo:min(hi, nl)]),
            "blocks": jax.tree_util.tree_map(lambda a: a[g0:g1],
                                             stack["blocks"]),
            "tail": tuple(stack["tail"]) if lo <= end < hi else (),
            "ln_f": stack["ln_f"]}


def split_params(params, cfg, start):
    """(UE half, edge half) of the parameters, cut before layer ``start``:
    the UE holds the embedding and layers [0, start), the edge layers
    [start, n_layers), the final norm and the head."""
    dec = params["decoder"]
    ue = {"embed": params["embed"],
          "decoder": _cut_stack(dec, cfg, 0, start)}
    edge = {"decoder": _cut_stack(dec, cfg, start, cfg.n_layers)}
    edge["lm_head" if not cfg.tie_embeddings else "embed"] = (
        params["embed"] if cfg.tie_embeddings else params["lm_head"])
    return ue, edge


def _prompt_positions(b, s):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def ue_forward(ue_params, cfg, tokens):
    """The UE half: embedding and the layers ``split_params`` gave it.
    Returns the hidden states (B, S, d) entering the first edge layer."""
    b, s = tokens.shape
    x = jnp.take(ue_params["embed"], tokens, axis=0).astype(
        dtype_of(cfg.compute_dtype))
    _, pattern, _, tail = layer_plan(cfg)
    stack = ue_params["decoder"]
    x, _, _ = _run_stack(stack, x, cfg, pattern, tail[:len(stack["tail"])],
                         positions=_prompt_positions(b, s), mode="train",
                         final_norm=False)
    return x


def edge_forward(edge_params, cfg, x, *, attn_len=None):
    """The edge half of a split prefill, the transformer counterpart of
    ``core.cnn.forward_from``: from the hidden states ``x`` (B, S, d) at
    the first layer ``split_params`` gave the edge, through its layers,
    the final norm and the head at each request's last position. Returns
    (float32 logits (B, vocab), the edge layers' cache at ``attn_len``
    slots, default S)."""
    b, s, _ = x.shape
    _, pattern, _, tail = layer_plan(cfg)
    stack = edge_params["decoder"]
    x, cache, _ = _run_stack(
        stack, x.astype(dtype_of(cfg.compute_dtype)), cfg, pattern,
        tail[:len(stack["tail"])], positions=_prompt_positions(b, s),
        mode="prefill", attn_len=attn_len or s, final_norm=False)
    x = apply_norm(stack["ln_f"], x[:, -1], cfg)
    return _logits(edge_params, cfg, x).astype(jnp.float32), cache


# ----------------------------------------------------------------- training
def loss_fn(params, cfg, batch):
    """batch: {"tokens": (B,S), "labels": (B,S) (-100 = ignore),
    optional "aux_embeds"}. Returns (loss, metrics)."""
    logits, _, aux = apply_model(
        params, cfg, batch["tokens"], aux_embeds=batch.get("aux_embeds"),
        mode="train")
    logits = logits.astype(jnp.float32)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = ((lse - tgt) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux,
                  "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0))}


# ------------------------------------------------------------------ serving
def prefill(params, cfg, tokens, *, attn_len, aux_embeds=None):
    """Full forward building the decode cache. Returns (last_logits, cache)."""
    logits, cache, _ = apply_model(
        params, cfg, tokens, aux_embeds=aux_embeds, mode="prefill",
        attn_len=attn_len)
    return logits[:, -1], cache


def decode_step(params, cfg, cache, token, idx):
    """One-token decode. token: (B, 1) int32; idx: scalar int32 absolute
    position of this token. Returns (logits (B, V), new_cache)."""
    logits, new_cache, _ = apply_model(
        params, cfg, token, mode="decode", cache=cache, idx=idx)
    return logits[:, 0], new_cache
