"""Entry ``llm_edge``: the edge half of a split transformer prefill, batch
after batch.

The window drives one compiled program per batch, as an edge server
would run it on the codes a batch of UEs uploaded: the Pallas
``ops.dequantize`` kernel, the autoencoder's ``compressor.decode``, then
``models.model.edge_forward`` from the first layer after the split point
through the layers this configuration holds, the final norm and the head
at each request's last position, returning the logits and the edge
layers' latent cache. The window, the release and the check's loop are
``edge.py``'s (``Edge`` here extends its class): a closed loop that keeps
two batches dispatched; a request counts as one task, as an image does in
the CNN cells.

Set-up draws everything from the seed on the device, with the reference's
own code: the UE half's weights (the embedding and the leading dense
layers) and the edge half's, a pool of requests of token ids uniform over
the vocabulary, their UE half, a PCA autoencoder at the configuration's
ratio for the split point (its eigendecomposition on the host), and the
8-bit codes of each pool batch with its (min, max). The UE half's weights
are dropped once the pool is made.
The program gets the same edge weights; nothing it makes is handed to
the reference.

After the window, the outputs of ``CHECK_BATCHES`` batches drawn from the
seed (the first of the six that ``edge.py``'s window keeps) are compared
with the reference, computed as the configuration
states (``moonlight.Arith()``: float32 activations, bfloat16 inputs to
every product, float32 accumulation; the codec's decode as the program's
configuration computes it, float32 at default precision):

* ``dequant_gap_steps``: the largest gap between the kernel's values and
  Eq. 2's, in code steps of that batch;
* ``cache_gap``: over the sampled requests, the largest distance between
  a request's latent ``c_kv`` in the first edge layer and the
  reference's, over the length of the reference's. It comes before any
  routing, so it holds every request to the limit;
* ``logits_gap``: the same for the last position's logits, over the
  requests whose last token the reference routes with a margin of at
  least ``TIE_MARGIN`` in every edge layer (between the k-th and the
  (k+1)-th of sigmoid score plus bias). At a margin below it the
  rounding of the bfloat16 products can pick the other expert, in the
  program or in the reference alike, and a different expert is a
  different answer of the same model: such a request is left out of
  ``logits_gap``, and how many were left out is printed.

``CHECK_BATCHES`` is 3, not ``edge.py``'s 6: the reference computes every
expert over every token (64 experts for 6 chosen), about ten times the
program's work a batch.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.reference import codec

_EDGE = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "edge.py"))
seed_key, reference_module = _EDGE.seed_key, _EDGE.reference_module
WINDOW_SPAN, SPAN_NAMES, POOL_BATCHES = \
    _EDGE.WINDOW_SPAN, _EDGE.SPAN_NAMES, _EDGE.POOL_BATCHES
CHECK_BATCHES = 3   # batches of the window compared with the reference
# routing margins under this are near-ties (the module's docstring): the
# program's and the reference's products differ in summation order, and
# bfloat16 rounding spreads that to about 1e-4 of the router's input
TIE_MARGIN = 1e-3


def _control():
    ref = harness.load_module(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reference", "moonlight.py"))
    return ref.Arith(jnp.bfloat16)


CONTROL = _control()


def program_config(c):
    """The program's ModelConfig for the published keys of ``c``: the
    registered architecture with the depth this configuration holds, and
    every width as ``c`` gives it."""
    import dataclasses

    from repro.configs import get_config

    base = get_config(c["program_arch"])
    moe = dataclasses.replace(
        base.moe, n_experts=int(c["n_routed_experts"]),
        top_k=int(c["num_experts_per_tok"]),
        d_expert=int(c["moe_intermediate_size"]),
        n_shared_experts=int(c["n_shared_experts"]),
        routed_scaling=float(c["routed_scaling_factor"]))
    return base.replace(
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]), vocab_size=int(c["vocab_size"]),
        q_lora_rank=int(c["q_lora_rank"] or 0),
        kv_lora_rank=int(c["kv_lora_rank"]),
        qk_nope_head_dim=int(c["qk_nope_head_dim"]),
        qk_rope_head_dim=int(c["qk_rope_head_dim"]),
        v_head_dim=int(c["v_head_dim"]), norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        first_dense_layers=int(c["first_k_dense_replace"]), moe=moe)


def program_params(edge):
    """The reference's edge weights in the program's layout (the same
    arrays): one stacked group of MoE blocks, the final norm, the head."""
    m = edge["moe"]
    block = {"ln1": {"scale": m["attn_norm"]},
             "attn": {"wq": m["q_proj"], "wkv_a": m["kv_a_proj"],
                      "kv_norm": m["kv_a_norm"], "wkv_b": m["kv_b_proj"],
                      "wo": m["o_proj"]},
             "ln2": {"scale": m["ffn_norm"]},
             "moe": {"router": m["router"], "router_bias": m["bias"],
                     "wg": m["gate"], "wi": m["up"], "wo": m["down"],
                     "shared_wg": m["shared_gate"],
                     "shared_wi": m["shared_up"],
                     "shared_wo": m["shared_down"]}}
    return {"decoder": {"lead": (), "blocks": (block,), "tail": (),
                        "ln_f": {"scale": edge["final_norm"]}},
            "lm_head": edge["lm_head"]}


def _first_ckv(out):
    """The first edge layer's latent from the program's cache, or from a
    reference's stacked c_kv."""
    c = out[2]
    return c["blocks"][0]["ckv"][0] if isinstance(c, dict) else c[0]


def _decode(dec, z):
    """The codec's decode as the program's configuration computes it."""
    return jnp.einsum("bsc,cd->bsd", z, dec.astype(z.dtype),
                      precision=jax.lax.Precision.DEFAULT)


class Edge(_EDGE.Edge):
    """Set-up on construction; then ``window``, ``release``, ``check``.
    ``arrays`` is the edge half's weights as the reference lays them out;
    ``program_params`` gives the program's layout of the same arrays."""

    def __init__(self, cell, seed):
        cfg, tr = cell.config, cell.traffic
        # the program's entry point first: a program without it fails here,
        # before the set-up's minute of drawing and compiling
        from repro.models.model import edge_forward  # noqa: F401
        self.pcfg = program_config(cfg)
        self.ref = ref = reference_module(cell)
        self.cfg = cfg
        self.limits = cell.limits
        self.bits = int(cfg["quant_bits"])
        self.batch = B = int(tr["batch"])
        self.tokens = T = int(tr["prompt_tokens"])
        self.pool = P = POOL_BATCHES
        point = int(tr["split"])
        self.start = int(cfg["split_after"][point - 1]) + 1
        if self.start != int(cfg["first_k_dense_replace"]):
            raise harness.BenchError(
                "the reference's UE half is the leading dense layers: the "
                "split must follow the last of them")
        d = int(cfg["hidden_size"])
        ratio = int(cfg["ae_ratio"][point - 1])
        vocab = int(cfg["vocab_size"])

        k_ue, k_edge, k_tok = jax.random.split(seed_key(seed), 3)
        with jax.default_matmul_precision("highest"):
            ue = jax.jit(lambda k: ref.init_ue(k, cfg))(k_ue)
            self.arrays = jax.jit(lambda k: ref.init_edge(k, cfg))(k_edge)
            keys = jax.random.split(k_tok, P)

            def ue_half(ue, k):
                tokens = jax.random.randint(k, (B, T), 0, vocab)
                return ref.ue_forward(ue, tokens, cfg)

            @jax.jit
            def covariance(ue, k):
                f = ue_half(ue, k).reshape(-1, d)
                f = f - f.mean(axis=0)
                return f.T @ f / f.shape[0]

            @jax.jit
            def make_codes(ue, keys, enc):
                return jax.lax.map(lambda k: codec.quantize(
                    ue_half(ue, k) @ enc, self.bits), keys)

            # the autoencoder is fitted on the first batch, by PCA as
            # codec.pca_autoencoder does, with the eigendecomposition on
            # the host: the TPU's compile of a 2048-wide eigh takes four
            # minutes. Batches then go through the UE half one at a time.
            _, vecs = np.linalg.eigh(np.asarray(covariance(ue, keys[0]),
                                                np.float64))
            enc = jnp.asarray(vecs[:, ::-1][:, :d // ratio], jnp.float32)
            self.dec = enc.T
            codes, mn, mx = make_codes(ue, keys, enc)
        del ue
        self.codes = [codes[i] for i in range(P)]
        self.mn = [mn[i] for i in range(P)]
        self.mx = [mx[i] for i in range(P)]
        del codes, mn, mx
        self.code_shape = tuple(self.codes[0].shape)
        self.n_layers = int(self.arrays["moe"]["bias"].shape[0])

        self.step = self._program_fn()
        rng = np.random.default_rng(seed)
        self.order = rng.permutation(P)
        # warm-up: the one shape the window uses, until two calls agree
        t_call = []
        for i in range(3):
            b = self.order[i % P]
            t0 = time.perf_counter()
            jax.block_until_ready(self.step(self.arrays, self.dec,
                                            self.codes[b], self.mn[b],
                                            self.mx[b]))
            t_call.append(time.perf_counter() - t0)
        self.t_step = min(t_call[1:])
        self._rng = rng
        self.kept = []
        self.steps = 0

    def _program_fn(self):
        """The program's edge half for one batch, jitted: the system under
        test."""
        from repro.core import compressor
        from repro.kernels import ops
        from repro.models import model

        pcfg, bits = self.pcfg, self.bits

        def edge_step(params, dec, codes, mn, mx):
            z = ops.dequantize(codes, mn, mx, bits=bits)
            feat = compressor.decode({"dec": dec}, z)
            logits, cache = model.edge_forward(program_params(params), pcfg,
                                               feat)
            return z, logits, cache

        return jax.jit(edge_step)

    def window(self, seconds, spans):
        """``edge.py``'s window; of the batches it keeps for the check,
        the first ``CHECK_BATCHES``."""
        e2e = super().window(seconds, spans)
        self.kept = self.kept[:CHECK_BATCHES]
        return e2e

    def info(self):
        """What the per-layer readers need of this cell."""
        return {"window_span": WINDOW_SPAN, "batches": self.steps,
                "batch": self.batch, "tokens": self.tokens,
                "edge_layers": self.n_layers,
                "code_shape": self.code_shape, "bits": self.bits}

    # ---------------------------------------------------------- the check
    def _reference_fn(self, ar, margins=False):
        ref, cfg, bits = self.ref, self.cfg, self.bits

        def fn(params, dec, codes, mn, mx):
            z = codec.dequantize(codes, mn, mx, bits, ar.dtype)
            logits, ckv, margin = ref.edge_forward(params, _decode(dec, z),
                                                   cfg, ar)
            return (z, logits, ckv, margin) if margins else (z, logits, ckv)

        jitted = jax.jit(fn)

        def call(*args):
            with jax.default_matmul_precision("highest"):
                return jitted(*args)

        return call

    def readings(self):
        """The compared numbers over the sampled batches: the program's
        outputs against the reference computed as the configuration
        states."""
        want_fn = self._reference_fn(self.ref.Arith(), margins=True)
        levels = (1 << self.bits) - 1
        deq, cg, lg, left_out, n = 0.0, 0.0, 0.0, 0, 0

        def gaps(a, b):
            a, b = (np.asarray(x, np.float64).reshape(x.shape[0], -1)
                    for x in (a, b))
            return np.linalg.norm(a - b, axis=1) / np.linalg.norm(b, axis=1)

        for b, out in self.kept:
            args = (self.arrays, self.dec, self.codes[b], self.mn[b],
                    self.mx[b])
            z, lgt, ckv = jax.device_get((out[0], out[1], _first_ckv(out)))
            z_ref, l_ref, ckv_ref, margin = jax.device_get(want_fn(*args))
            step = (float(self.mx[b]) - float(self.mn[b])) / levels
            deq = max(deq, float(np.max(np.abs(
                np.asarray(z, np.float64) - z_ref))) / step)
            cg = max(cg, float(np.max(gaps(ckv, ckv_ref[0]))))
            clear = np.all(margin >= TIE_MARGIN, axis=1)
            left_out += int(np.sum(~clear))
            n += len(clear)
            if clear.any():
                lg = max(lg, float(np.max(gaps(lgt, l_ref)[clear])))
        print(f"llm_edge: logits_gap over {n - left_out} of {n} requests; "
              f"{left_out} left out at a routing margin under {TIE_MARGIN}",
              file=sys.stderr, flush=True)
        if left_out == n:
            lg = float("inf")
        return {"dequant_gap_steps": deq, "cache_gap": cg, "logits_gap": lg}


def build(cell, seed):
    return Edge(cell, seed)
