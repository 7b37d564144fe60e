"""Per-module latency/energy tables (paper §3.4, Fig. 7).

The paper *measures* these on a Jetson Nano with an external power monitor.
No such hardware exists in this container, so the tables come from an
analytic device model

    t(module) = max(flops / (eff * peak_flops), bytes / mem_bw)
    e(module) = t * active_power

calibrated so a full ResNet18(224) inference costs ~50 ms / ~0.11 J on the
UE — the magnitudes behind the paper's T0 = 0.5 s (~10x a full local
inference) and beta = 0.47 (latency/energy ratio). For the assigned
transformer architectures the same model runs over per-layer FLOPs/bytes
derived from the ModelConfig; on the TPU-edge side the constants are v5e's.
"""
from __future__ import annotations

import dataclasses
from typing import List

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    name: str
    peak_flops: float           # effective FLOP/s (incl. utilization)
    mem_bw: float               # B/s
    active_power: float         # W while computing
    mem_bytes: float            # capacity for feasibility checks


# Jetson-Nano-like UE in 5 W low-power mode: ~236 GFLOPS fp16 peak, ~30%
# effective => 72 GFLOP/s; 25.6 GB/s LPDDR4; ~2.1 W above idle.
JETSON_NANO = DeviceModel("jetson-nano", 7.2e10, 2.56e10 * 0.6, 2.1, 4e9)

# A beefier UE tier (phone-class NPU) used for transformer-UE experiments.
PHONE_NPU = DeviceModel("phone-npu", 2.0e12, 5.0e10, 3.0, 8e9)

# Low-end IoT tier (Pi-Zero-class SoC): ~5 GFLOP/s effective, slow LPDDR2,
# little headroom above idle, 512 MB — most transformer splits are infeasible.
IOT_SOC = DeviceModel("iot-soc", 5.0e9, 2.0e9, 0.8, 5.12e8)

# TPU v5e edge chip (the "edge server" of the lifted scenario).
TPU_V5E = DeviceModel("tpu-v5e", 197e12 * 0.5, 819e9, 170.0, 16e9)

UE_TIERS = {d.name: d for d in (JETSON_NANO, PHONE_NPU, IOT_SOC)}


@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """Per-UE runtime profile the scheduler consumes: the device the UE's
    split table was built for plus the compute power draw the MEC env charges
    for local seconds (paper's P_compute; was a single global scalar)."""
    name: str
    p_compute: float            # W charged per local compute second
    device: DeviceModel = JETSON_NANO

    @classmethod
    def from_device(cls, dev: DeviceModel) -> "DeviceProfile":
        return cls(dev.name, dev.active_power, dev)


# Weaker edge tiers for multi-server pools: a rack GPU and a fanless NUC.
EDGE_GPU = DeviceModel("edge-gpu", 5.0e12, 3.0e11, 70.0, 1.2e10)
EDGE_NUC = DeviceModel("edge-nuc", 8.0e11, 6.0e10, 28.0, 8e9)


@dataclasses.dataclass(frozen=True)
class ServerProfile:
    """One edge server of an EdgePool, as the MEC env sees it.

    ``dist_scale`` multiplies each UE's distance for uplinks to THIS
    server (servers sit at different points of the cell), ``bw_scale``
    multiplies the per-channel bandwidth of this server's own uplink
    channels, and ``edge_speed`` is the effective FLOP/s the server
    devotes to finishing offloaded inferences — 0.0 keeps the paper's
    assumption of an instantaneous edge. A profile with all three at
    their defaults is the paper's single server: the env compiles the
    routing machinery out entirely and is bit-for-bit the seed env."""
    name: str
    device: DeviceModel = TPU_V5E
    dist_scale: float = 1.0
    bw_scale: float = 1.0
    edge_speed: float = 0.0      # 0.0 = instant edge (paper assumption)

    @property
    def is_paper_default(self) -> bool:
        return (self.dist_scale == 1.0 and self.bw_scale == 1.0
                and self.edge_speed == 0.0)

    @classmethod
    def from_device(cls, dev: DeviceModel, *, dist_scale=1.0, bw_scale=1.0,
                    utilization=0.3) -> "ServerProfile":
        """A server whose edge-side inference runs at ``utilization`` of
        the device's peak (edge chips juggle many tenants)."""
        return cls(dev.name, dev, dist_scale, bw_scale,
                   dev.peak_flops * utilization)


def module_time_energy(flops: float, bytes_moved: float, dev: DeviceModel):
    t = max(flops / dev.peak_flops, bytes_moved / dev.mem_bw)
    return t, t * dev.active_power


def task_latency_energy(l_b, n_b, rate, p_compute, p_tx, t_edge=None):
    """Eq. 7/8 closed-form per-task latency/energy — THE one definition.

    A task run at split b costs

        t = l_b + n_b / rate [+ t_edge]     (Eq. 7, + edge service)
        e = l_b * p_compute + (n_b / rate) * p_tx          (Eq. 8)

    where ``l_b`` is the UE-side local+compression seconds, ``n_b`` the
    offloaded bits, ``rate`` the uplink bits/s under the current
    interference, and ``t_edge`` the (processor-shared) edge service
    seconds (None or 0 for the paper's instantaneous edge).

    Shared by ``MECEnv.task_overhead``, ``rl.heuristics._joint_overhead``
    and the continuous-time stream simulator (``repro.stream.events``), so
    the three callers cannot drift; written with plain operators so it is
    exact on jnp float32 arrays and numpy float64 scalars alike. The op
    order (one division, reused) matches the historical env expression
    bit-for-bit on float32 inputs."""
    tx = n_b / rate
    t = l_b + tx
    if t_edge is not None:
        t = t + t_edge
    e = l_b * p_compute + tx * p_tx
    return t, e


# -------------------------------------------------- transformer layer costs
def mla_costs(cfg: ModelConfig, s: int, ctx: int, *, decode=False):
    """(flops, param elements) of one MLA layer's attention for ``s``
    query tokens against ``ctx`` keys. Prefill expands keys and values
    per head; decode (``decode``) attends in the latent space, the key
    up-projection absorbed into the query and the value one applied
    after, as ``models.attention.mla_decode`` does."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if cfg.q_lora_rank:
        q_par = d * cfg.q_lora_rank + cfg.q_lora_rank * h * (dn + dr)
    else:
        q_par = d * h * (dn + dr)
    par = q_par + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    if decode:
        fl = 2 * s * (q_par + d * (r + dr) + h * dn * r + h * r * dv
                      + h * dv * d)
        fl += 2 * s * ctx * h * (2 * r + dr)
    else:
        fl = 2 * s * par + 2 * s * ctx * h * (dn + dr + dv)
    return fl, par


def layer_costs(cfg: ModelConfig, seq_len: int) -> List[dict]:
    """Per-layer {flops, bytes, param_bytes} for a seq_len-token forward.
    bytes = params read once + activations in/out (bf16)."""
    d, dh = cfg.d_model, cfg.head_dim
    hq, hkv, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    s = seq_len
    act = 2 * s * d * 2  # in+out hidden, bf16
    out = []
    for bt in cfg.block_types():
        if bt == "mamba2":
            ss = cfg.ssm
            di = ss.expand * d
            h = di // ss.head_dim
            n = ss.d_state
            proj = 2 * s * d * (2 * di + 2 * n + h) + 2 * s * di * d
            ssd = 2 * s * h * ss.head_dim * n * 3 + 2 * s * ss.chunk * (
                n + h * ss.head_dim)
            pbytes = (d * (2 * di + 2 * n + h) + di * d) * 2
            out.append({"flops": proj + ssd, "bytes": pbytes + act,
                        "param_bytes": pbytes})
            continue
        if bt == "rec":
            drnn = d
            fl = 2 * s * d * drnn * 2 + 2 * s * drnn * drnn * 2 \
                + 2 * s * drnn * d + 6 * s * d * f
            pbytes = (2 * d * drnn + 2 * drnn * drnn + drnn * d + 3 * d * f) * 2
            out.append({"flops": fl, "bytes": pbytes + act,
                        "param_bytes": pbytes})
            continue
        # attention part
        attn_proj = 2 * s * d * (hq + 2 * hkv) * dh + 2 * s * hq * dh * d
        ctx = min(s, cfg.window) if bt == "lattn" else s
        attn_qk = 4 * s * ctx * hq * dh
        a_params = (d * (hq + 2 * hkv) * dh + hq * dh * d) * 2
        fl = attn_proj + attn_qk
        if cfg.mla and bt in ("dense", "moe"):
            fl, a_params = mla_costs(cfg, s, ctx)
            a_params *= 2
        pbytes = a_params
        if bt in ("xattn",):
            fl = 2 * s * d * hq * dh + 2 * s * hq * dh * d \
                + 4 * s * cfg.n_aux_tokens * hq * dh \
                + 2 * cfg.n_aux_tokens * d * 2 * hkv * dh
        if bt == "decx":
            nf = cfg.encoder.n_frames if cfg.encoder else 0
            fl += 2 * s * d * hq * dh + 2 * s * hq * dh * d \
                + 4 * s * nf * hq * dh
            pbytes += a_params
        # ffn part
        if bt == "moe":
            m = cfg.moe
            ffl = 2 * s * d * m.n_experts  # router
            ffl += 6 * s * d * m.d_expert * (m.top_k + m.n_shared_experts)
            fp = (m.n_experts + m.n_shared_experts) * 3 * d * m.d_expert * 2
            # only the activated experts' weights stream from memory
            fbytes = 3 * d * m.d_expert * (m.top_k + m.n_shared_experts) * 2
        elif bt == "mamba2":
            ffl, fp, fbytes = 0, 0, 0
        else:
            mult = 3 if cfg.act == "swiglu" else 2
            ffl = mult * 2 * s * d * f
            fp = mult * d * f * 2
            fbytes = fp
        out.append({"flops": fl + ffl, "bytes": pbytes + fbytes + act,
                    "param_bytes": pbytes + fp})
    return out


def decode_layer_costs(cfg: ModelConfig, ctx_len: int) -> List[dict]:
    """Per-layer {flops, bytes, param_bytes} of ONE decode step at context
    length ``ctx_len``: s = 1 projections, attention scores over the
    (window-capped) context, and the layer's serving-cache bytes READ per
    token — decode is memory-bound, so the cache traffic is the term that
    grows with context. SSM/RG-LRU layers update O(1) state and are
    constant in ctx_len."""
    d, dh = cfg.d_model, cfg.head_dim
    hq, hkv, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    act = 2 * d * 2  # in+out hidden for the single token, bf16
    kv_el = 1 if cfg.kv_quant_bits else 2   # int8 codes vs bf16
    out = []
    for bt in cfg.block_types():
        if bt == "mamba2":
            ss = cfg.ssm
            di = ss.expand * d
            h = di // ss.head_dim
            n = ss.d_state
            proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
            step = 2 * h * ss.head_dim * n * 3
            pbytes = (d * (2 * di + 2 * n + h) + di * d) * 2
            state_b = h * ss.head_dim * n * 4 \
                + (ss.d_conv - 1) * (di + 2 * n) * 2
            out.append({"flops": proj + step,
                        "bytes": pbytes + state_b + act,
                        "param_bytes": pbytes})
            continue
        if bt == "rec":
            drnn = d
            fl = 2 * d * drnn * 2 + 2 * drnn * drnn * 2 + 2 * drnn * d \
                + 6 * d * f
            pbytes = (2 * d * drnn + 2 * drnn * drnn + drnn * d
                      + 3 * d * f) * 2
            out.append({"flops": fl, "bytes": pbytes + drnn * 4 + act,
                        "param_bytes": pbytes})
            continue
        # attention part: project the new token, score it against the cache
        ctx = min(ctx_len, cfg.window) if bt == "lattn" else ctx_len
        attn_proj = 2 * d * (hq + 2 * hkv) * dh + 2 * hq * dh * d
        attn_qk = 4 * ctx * hq * dh
        a_params = (d * (hq + 2 * hkv) * dh + hq * dh * d) * 2
        cache_b = 2 * ctx * hkv * dh * kv_el \
            + (2 * ctx * hkv * 4 if cfg.kv_quant_bits else 0)
        fl = attn_proj + attn_qk
        if cfg.mla and bt in ("dense", "moe"):
            fl, a_params = mla_costs(cfg, 1, ctx, decode=True)
            a_params *= 2
            cache_b = ctx * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) \
                * (4 if cfg.compute_dtype == "float32" else 2)
        pbytes = a_params
        if bt == "xattn":
            fl = 2 * d * hq * dh + 2 * hq * dh * d \
                + 4 * cfg.n_aux_tokens * hq * dh
            cache_b = 2 * cfg.n_aux_tokens * hkv * dh * 2
        if bt == "decx":
            nf = cfg.encoder.n_frames if cfg.encoder else 0
            fl += 2 * d * hq * dh + 2 * hq * dh * d + 4 * nf * hq * dh
            pbytes += a_params
            cache_b += 2 * nf * hkv * dh * 2
        # ffn part
        if bt == "moe":
            m = cfg.moe
            ffl = 2 * d * m.n_experts  # router
            ffl += 6 * d * m.d_expert * (m.top_k + m.n_shared_experts)
            fp = (m.n_experts + m.n_shared_experts) * 3 * d * m.d_expert * 2
            fbytes = 3 * d * m.d_expert * (m.top_k + m.n_shared_experts) * 2
        else:
            mult = 3 if cfg.act == "swiglu" else 2
            ffl = mult * 2 * d * f
            fp = mult * d * f * 2
            fbytes = fp
        out.append({"flops": fl + ffl,
                    "bytes": pbytes + fbytes + cache_b + act,
                    "param_bytes": pbytes + fp})
    return out


def embed_costs(cfg: ModelConfig, seq_len: int) -> dict:
    pb = cfg.vocab_size * cfg.d_model * 2
    return {"flops": 2 * seq_len * cfg.d_model * cfg.vocab_size,  # lm head
            "bytes": pb * 2, "param_bytes": pb * (1 if cfg.tie_embeddings else 2)}
