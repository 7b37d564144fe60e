"""Moonlight-16B-A3B (moonshotai, ``model_type`` deepseek_v3), written from
the published DeepSeek-V3 modelling description as the plain reference of
the ``moonlight16b-a3b-*`` configurations: no kernel, cache or batching of
the program's, and nothing imported from it.

A layer is ``x += MLA(RMSNorm(x))`` then ``x += FFN(RMSNorm(x))``; layers
below ``first_k_dense_replace`` have a SwiGLU FFN of ``intermediate_size``,
the others a mixture of experts. The last layer is followed by a final
RMSNorm and an untied head.

* MLA, without a query low-rank path (``q_lora_rank`` null): q = x W_q,
  heads of ``qk_nope_head_dim + qk_rope_head_dim``; [c_kv | k_pe] =
  x W_kv_a, c_kv RMS-normed (``kv_a_layernorm``); [k_nope | v] =
  c_kv W_kv_b per head; RoPE on q_pe and on the one k_pe every head
  shares, in the published code's layout (the rotary dimensions are
  de-interleaved, then ``rotate_half``); causal softmax attention scaled
  by 1/sqrt(nope + rope dims); then W_o.
* Router (``noaux_tc``, one group): s = sigmoid(h W_g); the experts are
  the top ``num_experts_per_tok`` of s + ``e_score_correction_bias``;
  their weights are s over its sum over the chosen (``norm_topk_prob``),
  times ``routed_scaling_factor``. Each expert is a SwiGLU of
  ``moe_intermediate_size``, computed over every token and masked by
  these weights; the ``n_shared_experts`` form one SwiGLU of their summed
  width, added unweighted.

An :class:`Arith` says how it computes. ``dtype`` holds activations, the
residual stream, norms, router scores and the softmax; every matrix
product takes the weights' dtype (bfloat16) as its inputs and
accumulates in float32, at the highest precision; the router's product
takes ``dtype`` inputs. The configuration states ``Arith(float32)``.
Departures from the published code, all of them where the products'
inputs are rounded, none in the mathematics: the softmax scale is folded
into the bfloat16 query, and its normaliser divides the product of the
bfloat16 numerators with the values (as flash-style attention computes
it), where the published code scales the scores and casts normalised
probabilities. ``Arith(bfloat16)`` is the control: everything, router and
residual included, in bfloat16.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arith:
    dtype: object = jnp.float32

    def cast(self, x):
        return jnp.asarray(x).astype(self.dtype)

    def mm(self, subscripts, x, w):
        """A product with ``x`` in ``w``'s dtype, float32 accumulation,
        the result in ``dtype``."""
        return jnp.einsum(subscripts, x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32,
                          precision=HIGHEST).astype(self.dtype)


# ------------------------------------------------------------- weights
# the scales the weights are drawn at: N(0, 1) embeddings; every product
# N(0, 1/fan_in), so each layer adds an update of the residual stream's
# order; the router N(0, 1/fan_in) in float32 with a correction bias of
# N(0, BIAS_STD^2), wide enough to move some of the top-6; norm scales
# near identity, so that a fault that drops one shows
BIAS_STD = 0.05
NORM_STD = 0.1


def _normal(key, shape, fan_in, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32)
            / np.sqrt(fan_in)).astype(dtype)


def _norm_scale(key, d):
    return (1.0 + NORM_STD * jax.random.normal(key, (d,), jnp.float32)
            ).astype(jnp.bfloat16)


def _dims(c):
    return (int(c["hidden_size"]), int(c["num_attention_heads"]),
            int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
            int(c["v_head_dim"]), int(c["kv_lora_rank"]))


def init_attn(key, c):
    d, h, dn, dr, dv, r = _dims(c)
    ks = jax.random.split(key, 8)
    return {"attn_norm": _norm_scale(ks[0], d),
            "q_proj": _normal(ks[1], (d, h * (dn + dr)), d),
            "kv_a_proj": _normal(ks[2], (d, r + dr), d),
            "kv_a_norm": _norm_scale(ks[3], r),
            "kv_b_proj": _normal(ks[4], (r, h * (dn + dv)), r),
            "o_proj": _normal(ks[5], (h * dv, d), h * dv),
            "ffn_norm": _norm_scale(ks[6], d)}


def _swiglu(key, d, f, lead=()):
    ks = jax.random.split(key, 3)
    return {"gate": _normal(ks[0], lead + (d, f), d),
            "up": _normal(ks[1], lead + (d, f), d),
            "down": _normal(ks[2], lead + (f, d), f)}


def init_dense_layer(key, c):
    k1, k2 = jax.random.split(key)
    return dict(init_attn(k1, c), **_swiglu(k2, int(c["hidden_size"]),
                                            int(c["intermediate_size"])))


def init_moe_layer(key, c):
    d, e = int(c["hidden_size"]), int(c["n_routed_experts"])
    f = int(c["moe_intermediate_size"])
    ks = jax.random.split(key, 5)
    shared = _swiglu(ks[2], d, f * int(c["n_shared_experts"]))
    return dict(init_attn(ks[0], c), **_swiglu(ks[1], d, f, (e,)),
                router=_normal(ks[3], (d, e), d, jnp.float32),
                bias=BIAS_STD * jax.random.normal(ks[4], (e,), jnp.float32),
                **{"shared_" + k: v for k, v in shared.items()})


def init_ue(key, c):
    """The UE half: the embedding and the leading dense layers."""
    k1, k2 = jax.random.split(key)
    n = int(c["first_k_dense_replace"])
    return {"embed": _normal(k1, (int(c["vocab_size"]),
                                  int(c["hidden_size"])), 1),
            "dense": [init_dense_layer(k, c)
                      for k in jax.random.split(k2, n)]}


def init_edge(key, c):
    """The edge half: the MoE layers this configuration holds, stacked on
    a leading axis and drawn one at a time, the final norm and the head."""
    k1, k2, k3 = jax.random.split(key, 3)
    n = int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"])
    d = int(c["hidden_size"])
    return {"moe": jax.lax.map(lambda k: init_moe_layer(k, c),
                               jax.random.split(k1, n)),
            "final_norm": _norm_scale(k2, d),
            "lm_head": _normal(k3, (d, int(c["vocab_size"])), d)}


# -------------------------------------------------------------- forward
def rms_norm(x, scale, eps, ar):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + jnp.asarray(eps, x.dtype)) \
        * ar.cast(scale)


def rope(x, theta, ar):
    """RoPE on (B, T, ..., D) at positions 0..T-1, as the published code:
    frequencies base^(-2i/D) in float32, the rotary dimensions
    de-interleaved ([x0, x2, ..., x1, x3, ...]), then x cos + rotate_half
    (x) sin."""
    t, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], axis=-1)
    shape = (1, t) + (1,) * (x.ndim - 3) + (dim,)
    cos, sin = (ar.cast(f(emb)).reshape(shape) for f in (jnp.cos, jnp.sin))
    x = x.reshape(x.shape[:-1] + (dim // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (dim,))
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + half * sin


def mla(p, x, c, ar):
    """Returns (attention output (B, T, d), c_kv (B, T, kv_lora_rank))."""
    b, t, _ = x.shape
    _, h, dn, dr, dv, r = _dims(c)
    eps = float(c["rms_norm_eps"])
    q = ar.mm("btd,dk->btk", x, p["q_proj"]).reshape(b, t, h, dn + dr)
    kva = ar.mm("btd,dk->btk", x, p["kv_a_proj"])
    c_kv = rms_norm(kva[..., :r], p["kv_a_norm"], eps, ar)
    kv = ar.mm("btr,rk->btk", c_kv, p["kv_b_proj"]).reshape(
        b, t, h, dn + dv)
    theta = float(c["rope_theta"])
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta, ar)], -1)
    k_pe = rope(kva[..., r:], theta, ar)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None, :],
                                        (b, t, h, dr))], -1)
    bf = jnp.bfloat16
    scale = jnp.asarray((dn + dr) ** -0.5, bf)
    s = jnp.einsum("bqhk,bshk->bhqs", (q.astype(bf) * scale), k.astype(bf),
                   preferred_element_type=jnp.float32,
                   precision=HIGHEST).astype(ar.dtype)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
    num = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o = jnp.einsum("bhqs,bshv->bqhv", num.astype(bf),
                   kv[..., dn:].astype(bf),
                   preferred_element_type=jnp.float32,
                   precision=HIGHEST).astype(ar.dtype)
    o = o / jnp.sum(num, axis=-1).transpose(0, 2, 1)[..., None]
    out = ar.mm("btk,kd->btd", o.astype(bf).reshape(b, t, h * dv),
                p["o_proj"])
    return out, c_kv


def swiglu(x, gate, up, down, ar):
    hid = jax.nn.silu(ar.mm("td,df->tf", x, gate)) \
        * ar.mm("td,df->tf", x, up)
    return ar.mm("tf,fd->td", hid, down)


def route(p, x, c, ar):
    """(weights (T, E), zero off each token's chosen experts; the margin
    (T,) between the k-th and (k+1)-th of s + bias)."""
    k, e = int(c["num_experts_per_tok"]), int(c["n_routed_experts"])
    z = jnp.einsum("td,de->te", x, ar.cast(p["router"]), precision=HIGHEST)
    s = jax.nn.sigmoid(z)
    ranked = s + ar.cast(p["bias"])
    top, idx = jax.lax.top_k(ranked, k + 1)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx[:, :k]].set(True)
    w = jnp.where(chosen, s, 0)
    w = w / jnp.sum(w, axis=-1, keepdims=True) \
        * ar.cast(c["routed_scaling_factor"])
    return w, (top[:, k - 1] - top[:, k]).astype(jnp.float32)


def moe(p, x, c, ar):
    """Returns (FFN output (T, d), routing margin (T,))."""
    w, margin = route(p, x, c, ar)

    def expert(acc, ex):
        we, gate, up, down = ex
        return acc + we[:, None] * swiglu(x, gate, up, down, ar), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (w.T, p["gate"], p["up"], p["down"]))
    shared = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"],
                    ar)
    return out + shared, margin


def layer(p, x, c, ar, dense):
    """One decoder layer. Returns (x, c_kv, routing margin (B, T) or
    None)."""
    b, t, d = x.shape
    eps = float(c["rms_norm_eps"])
    a, c_kv = mla(p, rms_norm(x, p["attn_norm"], eps, ar), c, ar)
    x = x + a
    h = rms_norm(x, p["ffn_norm"], eps, ar).reshape(b * t, d)
    if dense:
        f, margin = swiglu(h, p["gate"], p["up"], p["down"], ar), None
    else:
        f, margin = moe(p, h, c, ar)
        margin = margin.reshape(b, t)
    return x + f.reshape(b, t, d), c_kv, margin


def ue_forward(params, tokens, c, ar=Arith()):
    """Hidden states (B, T, d) after the embedding and dense layers."""
    x = ar.cast(jnp.take(params["embed"], tokens, axis=0))
    for p in params["dense"]:
        x, _, _ = layer(p, x, c, ar, dense=True)
    return x


def edge_forward(params, x, c, ar=Arith()):
    """From hidden states (B, T, d) at the first MoE layer: (float32
    logits at the last position (B, vocab), each layer's c_kv (L, B, T,
    r), each layer's routing margin at the last position (B, L))."""
    x = ar.cast(x)
    ckv, margins = [], []
    for i in range(params["moe"]["bias"].shape[0]):
        p = jax.tree_util.tree_map(lambda a: a[i], params["moe"])
        x, c_kv, margin = layer(p, x, c, ar, dense=False)
        ckv.append(c_kv)
        margins.append(margin[:, -1])
    x = rms_norm(x[:, -1], params["final_norm"], float(c["rms_norm_eps"]),
                 ar)
    logits = ar.mm("bd,dv->bv", x, params["lm_head"])
    return (logits.astype(jnp.float32), jnp.stack(ckv),
            jnp.stack(margins, axis=1))
