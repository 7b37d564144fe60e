"""DNN decoupling: split plans and the per-split overhead tables that define
the RL environment's action space (paper §3.2-3.4).

A split decision b in {0, 1, ..., B+1} means (paper convention):
  b = 0    offload the raw input
  b = k    run modules/layers up to candidate point k on the UE, compress the
           boundary feature with the AE (+quantization), transmit
  b = B+1  full local inference

``split_table`` builds, for a backbone (CNN or assigned transformer arch),
the arrays {t_local, e_local, t_comp, e_comp, f_bits, feasible} the MEC env
consumes. Architecture-family constraints (DESIGN.md §6):
  * MoE archs: a split is feasible only if the UE-side parameter bytes fit
    UE memory (expert banks usually force b=0).
  * VLM: splits below the last cross-attn layer ship the image embeddings
    (compressed at the same rate) alongside the boundary feature.
  * enc-dec: the encoder runs on the UE for any decoder-side split; b=0
    ships the (stub) mel frames.
  * SSM / hybrid: boundary additionally carries the recurrent state.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Union

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import overhead as oh
from repro.core.cnn import CNNModel


@dataclasses.dataclass
class SplitPlan:
    name: str
    # candidate boundaries; entry k (1-based) = number of UE-side modules
    points: List[int]
    t_local: np.ndarray          # (B+2,) cumulative UE compute latency
    e_local: np.ndarray
    t_comp: np.ndarray           # compressor latency at each b
    e_comp: np.ndarray
    f_bits: np.ndarray           # offload payload (bits); 0 for b = B+1
    feasible: np.ndarray         # bool (B+2,)
    device: Optional[str] = None  # UE device the tables were built for

    @property
    def n_actions(self):
        return len(self.f_bits)


def _finalize(name, points, rows, device=None):
    t_l, e_l, t_c, e_c, fb, feas = (np.array([r[i] for r in rows])
                                    for i in range(6))
    if t_l[0] != 0.0:
        raise ValueError(f"{name}: raw offload (b=0) must cost no UE compute")
    if np.any(np.diff(t_l[1:-1]) < -1e-9):
        raise ValueError(f"{name}: cumulative t_local must be monotone over "
                         f"split points, got {t_l[1:-1]}")
    if fb[-1] != 0.0:
        raise ValueError(f"{name}: full-local (b=B+1) must offload 0 bits")
    return SplitPlan(name, points, t_l, e_l, t_c, e_c, fb,
                     feas.astype(bool), device=device)


# ------------------------------------------------------------------- fleets
@dataclasses.dataclass
class FleetPlan:
    """Per-UE split tables for a heterogeneous fleet, padded to a shared
    action space. Layout of each (B_max+2,) row: index 0 = raw offload,
    indices 1..B = that UE's split points, then infeasible padding, and the
    LAST index is always full-local — so b = n_actions-1 means "run locally"
    for every UE regardless of how many split points its backbone exposes."""
    names: List[str]
    profiles: List[oh.DeviceProfile]
    t_local: np.ndarray          # (N, B_max+2)
    e_local: np.ndarray
    t_comp: np.ndarray
    e_comp: np.ndarray
    f_bits: np.ndarray
    feasible: np.ndarray         # (N, B_max+2) bool; False on padding
    p_compute: np.ndarray        # (N,) W per local compute second

    @property
    def n_ue(self):
        return len(self.names)

    @property
    def n_actions(self):
        return self.t_local.shape[1]


def _pad_row(vals: np.ndarray, width: int, fill=0.0) -> np.ndarray:
    """Pad a (B+2,) table to (width,) keeping the last entry (full-local)
    last; padding goes between the split points and full-local."""
    out = np.full((width,), fill, dtype=np.float64)
    out[: len(vals) - 1] = vals[:-1]
    out[-1] = vals[-1]
    return out


def build_fleet(plans: Sequence[SplitPlan],
                profiles: Optional[Sequence[Union[oh.DeviceProfile,
                                                  oh.DeviceModel]]] = None
                ) -> FleetPlan:
    """Stack an arbitrary mix of SplitPlans (different backbones, different
    B) into per-UE tables. Padded action slots are marked infeasible and cost
    nothing, so a policy that respects the mask never sees them."""
    if not plans:
        raise ValueError("build_fleet needs at least one SplitPlan")
    if profiles is None:
        profiles = [oh.DeviceProfile.from_device(oh.JETSON_NANO)] * len(plans)
    if len(profiles) != len(plans):
        raise ValueError(f"{len(plans)} plans but {len(profiles)} profiles")
    profiles = [p if isinstance(p, oh.DeviceProfile)
                else oh.DeviceProfile.from_device(p) for p in profiles]
    for plan, prof in zip(plans, profiles):
        if plan.device is not None and prof.device.name != plan.device:
            raise ValueError(
                f"plan '{plan.name}' has tables built for {plan.device} but "
                f"its profile is {prof.device.name}; rebuild the split table "
                f"with dev/ue_dev={prof.device.name}")
    width = max(p.n_actions for p in plans)
    stack = {f: np.stack([_pad_row(getattr(p, f), width) for p in plans])
             for f in ("t_local", "e_local", "t_comp", "e_comp", "f_bits")}
    feas = np.zeros((len(plans), width), dtype=bool)
    for i, p in enumerate(plans):
        feas[i, : p.n_actions - 1] = p.feasible[:-1]
        feas[i, -1] = p.feasible[-1]
    return FleetPlan(
        names=[p.name for p in plans], profiles=list(profiles),
        feasible=feas,
        p_compute=np.array([pr.p_compute for pr in profiles]), **stack)


def homogeneous_fleet(plan: SplitPlan, n_ue: int,
                      profile: Optional[Union[oh.DeviceProfile,
                                              oh.DeviceModel]] = None
                      ) -> FleetPlan:
    """The seed scenario as a special case: N identical plans/devices. The
    default profile follows the device the plan was built for."""
    if profile is None:
        if plan.device is None:
            dev = oh.JETSON_NANO
        elif plan.device in oh.UE_TIERS:
            dev = oh.UE_TIERS[plan.device]
        else:
            raise ValueError(
                f"plan '{plan.name}' was built for '{plan.device}', which is "
                f"not a known UE tier {sorted(oh.UE_TIERS)}; pass an explicit "
                f"DeviceProfile")
        prof = oh.DeviceProfile.from_device(dev)
    else:
        prof = profile
    return build_fleet([plan] * n_ue, [prof] * n_ue)


# --------------------------------------------------------------------- CNN
def cnn_split_table(model: CNNModel, in_size: int, *,
                    dev=oh.JETSON_NANO, ae_ratio=(16, 12, 8, 4),
                    quant_bits=8, batch=1,
                    input_bits_per_px=8) -> SplitPlan:
    """ae_ratio: per-split-point channel-reduction factors R_c. Defaults
    mirror the paper's Fig. 4 (R up to ~128 at early points, decreasing with
    depth: the AE compresses early features best). May be a scalar."""
    flops = model.module_flops(in_size)
    shapes = model.feature_shapes(in_size)
    points = list(model.split_after)
    if not hasattr(ae_ratio, "__len__"):
        ae_ratio = [ae_ratio] * len(points)
    rows = []
    # b = 0: raw input offload
    raw_bits = batch * 3 * in_size * in_size * input_bits_per_px
    rows.append((0.0, 0.0, 0.0, 0.0, raw_bits, True))
    for pi, k in enumerate(points):
        fl = sum(flops[:k + 1]) * batch
        t, e = oh.module_time_energy(fl, fl / 8, dev)
        c, h, w = shapes[k]
        cp = max(1, c // ae_ratio[pi])
        enc_fl = 2 * c * cp * h * w * batch
        tc, ec = oh.module_time_energy(enc_fl, enc_fl / 4, dev)
        bits = batch * cp * h * w * quant_bits
        rows.append((t, e, tc, ec, bits, True))
    fl = sum(flops) * batch
    t, e = oh.module_time_energy(fl, fl / 8, dev)
    rows.append((t, e, 0.0, 0.0, 0.0, True))
    return _finalize(model.name, points, rows, device=dev.name)


def cnn_jalad_table(model: CNNModel, in_size: int, *, dev=oh.JETSON_NANO,
                    entropy_bits=5.0, batch=1) -> SplitPlan:
    """JALAD baseline: 8-bit quant + entropy coding; no channel reduction;
    coder latency from symbols/s throughput (the paper's Fig. 7 point that
    entropy coding on large features dominates)."""
    from repro.core.jalad import ENTROPY_CODER_SYMBOLS_PER_S as CPS
    flops = model.module_flops(in_size)
    shapes = model.feature_shapes(in_size)
    points = list(model.split_after)
    rows = []
    raw_bits = batch * 3 * in_size * in_size * 8
    rows.append((0.0, 0.0, 0.0, 0.0, raw_bits, True))
    for k in points:
        fl = sum(flops[:k + 1]) * batch
        t, e = oh.module_time_energy(fl, fl / 8, dev)
        c, h, w = shapes[k]
        n = batch * c * h * w
        tc = n / CPS
        ec = tc * dev.active_power
        rows.append((t, e, tc, ec, n * entropy_bits, True))
    fl = sum(flops) * batch
    t, e = oh.module_time_energy(fl, fl / 8, dev)
    rows.append((t, e, 0.0, 0.0, 0.0, True))
    return _finalize(model.name + "-jalad", points, rows, device=dev.name)


# ------------------------------------------------------------- transformers
def transformer_split_table(cfg: ModelConfig, *, seq_len=128,
                            ue_dev=oh.PHONE_NPU, n_points=4,
                            ae_ratio=None, quant_bits=None,
                            batch=1) -> SplitPlan:
    ae_ratio = ae_ratio or cfg.bottleneck_ratio
    quant_bits = quant_bits or cfg.quant_bits
    layers = oh.layer_costs(cfg, seq_len)
    L = len(layers)
    emb = oh.embed_costs(cfg, seq_len)
    btypes = cfg.block_types()
    points = [max(1, round(L * (i + 1) / (n_points + 1)))
              for i in range(n_points)]

    embed_pb = cfg.vocab_size * cfg.d_model * 2
    cum_fl = np.cumsum([l["flops"] for l in layers]) * batch
    cum_pb = np.cumsum([l["param_bytes"] for l in layers])

    # family extras
    last_x = max((i for i, bt in enumerate(btypes) if bt in ("xattn",)),
                 default=-1)
    aux_bits_raw = 0
    if cfg.family == "vlm":
        aux_bits_raw = cfg.n_aux_tokens * cfg.d_model * 16 * batch
    enc_flops = 0
    if cfg.family == "encdec":
        enc_layers = oh.layer_costs(
            cfg.replace(block_pattern=("dense",), n_layers=cfg.encoder.n_layers),
            cfg.encoder.n_frames)
        enc_flops = sum(l["flops"] for l in enc_layers) * batch
        aux_bits_raw = cfg.encoder.n_frames * cfg.d_model * 16 * batch

    rows = []
    # b = 0: raw input (token ids; for audio the stub mel frames)
    if cfg.family == "encdec":
        raw_bits = cfg.encoder.n_frames * 80 * 32 * batch + seq_len * 32 * batch
    elif cfg.family == "vlm":
        # raw pixels for 1600 patches ~ (patch 14x14x3 @8bit)
        raw_bits = cfg.n_aux_tokens * 14 * 14 * 3 * 8 * batch + seq_len * 32 * batch
    else:
        raw_bits = seq_len * 32 * batch
    rows.append((0.0, 0.0, 0.0, 0.0, raw_bits, True))

    d = cfg.d_model
    dprime = max(1, d // ae_ratio)
    rate = (d * 32.0) / (dprime * quant_bits)
    for k in points:
        fl = cum_fl[k - 1] + (enc_flops if cfg.family == "encdec" else 0)
        t, e = oh.module_time_energy(fl, fl / 4, ue_dev)
        enc_fl = 2 * seq_len * d * dprime * batch
        tc, ec = oh.module_time_energy(enc_fl, enc_fl / 4, ue_dev)
        bits = seq_len * dprime * quant_bits * batch
        # NOTE: recurrent/SSM state does NOT cross the boundary — layer i's
        # state is internal to layer i; edge-side layers recompute their own
        # states from the transmitted hidden sequence. Only context extras
        # (image embeds / encoder output) ship.
        if cfg.family == "vlm" and k <= last_x:
            bits += aux_bits_raw * 32 / (16 * rate)   # embeds, AE+quant'ed
        if cfg.family == "encdec":
            bits += cfg.encoder.n_frames * dprime * quant_bits * batch
        ue_pb = embed_pb + cum_pb[k - 1]
        rows.append((t, e, tc, ec, bits, ue_pb <= ue_dev.mem_bytes))
    fl_full = cum_fl[-1] + emb["flops"] * batch \
        + (enc_flops if cfg.family == "encdec" else 0)
    t, e = oh.module_time_energy(fl_full, fl_full / 4, ue_dev)
    total_pb = embed_pb + cum_pb[-1] + (emb["param_bytes"] - embed_pb)
    rows.append((t, e, 0.0, 0.0, 0.0, total_pb <= ue_dev.mem_bytes))
    return _finalize(cfg.name, points, rows, device=ue_dev.name)


def split_table(target, **kw) -> SplitPlan:
    """target: CNNModel or ModelConfig."""
    if isinstance(target, CNNModel):
        return cnn_split_table(target, kw.pop("in_size", 224), **kw)
    return transformer_split_table(target, **kw)


# ------------------------------------------------------- measured tables
def measured_cnn_module_costs(model: CNNModel, in_size: int, *,
                              batch=1) -> List[dict]:
    """Per-module {flops, bytes_accessed, hlo_dot_flops} from XLA itself:
    each module's forward is lowered + compiled against abstract params
    (nothing is materialized or executed) and the compiled cost analysis
    read out via launch.hloanalysis.compiled_costs. Unlike
    CNNModel.module_flops (the hand-derived conv walker), this counts
    everything XLA will actually run — BN reductions, elementwise ops,
    padding copies — and it is the same pipeline launch/dryrun.py records
    for the assigned transformer archs."""
    import jax
    from repro.launch.hloanalysis import compiled_costs

    pstruct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shapes = model.feature_shapes(in_size)
    costs = []
    for i in range(model.n_modules):
        in_shape = ((batch, 3, in_size, in_size) if i == 0
                    else (batch,) + tuple(shapes[i - 1]))
        x = jax.ShapeDtypeStruct(in_shape, np.float32)

        def mod_fwd(p, x, _i=i):
            return model.run_module(p, _i, x)

        costs.append(compiled_costs(mod_fwd, pstruct[i], x))
    return costs


def measured_cnn_split_table(model: CNNModel, in_size: int, *,
                             dev=oh.JETSON_NANO, rd=None,
                             ae_ratio=(16, 12, 8, 4), quant_bits=8,
                             batch=1, input_bits_per_px=8,
                             module_costs=None) -> SplitPlan:
    """``cnn_split_table`` with MEASURED inputs instead of paper constants:

    * per-module FLOPs and bytes from the compiled-HLO cost analysis
      (``measured_cnn_module_costs``) through the same
      ``core.overhead.module_time_energy`` device model — in particular
      the memory side uses XLA's real bytes-accessed instead of the
      flops/8 heuristic;
    * per-split-point compressor rate-distortion from a measured sweep
      (``core.compressor.measure_rate_distortion``: trained AE at each
      candidate point, rate selected by the paper's 2%-accuracy rule),
      passed as ``rd``; the paper's ``ae_ratio`` constants remain the
      fallback when ``rd`` is None.

    Opt-in: the default ``cnn_split_table`` (paper constants) is untouched
    and stays golden-pinned. ``module_costs`` lets callers reuse a sweep."""
    costs = (measured_cnn_module_costs(model, in_size, batch=batch)
             if module_costs is None else module_costs)
    shapes = model.feature_shapes(in_size)
    points = list(model.split_after)
    if rd is not None and len(rd) != len(points):
        raise ValueError(f"rd has {len(rd)} rows for {len(points)} points")
    if not hasattr(ae_ratio, "__len__"):
        ae_ratio = [ae_ratio] * len(points)
    cum_fl = np.cumsum([c["flops"] for c in costs])
    cum_by = np.cumsum([c["bytes_accessed"] for c in costs])
    rows = []
    raw_bits = batch * 3 * in_size * in_size * input_bits_per_px
    rows.append((0.0, 0.0, 0.0, 0.0, raw_bits, True))
    for pi, k in enumerate(points):
        t, e = oh.module_time_energy(cum_fl[k], cum_by[k], dev)
        c, h, w = shapes[k]
        if rd is not None:
            cp = int(rd[pi]["ch_prime"])
            q = int(rd[pi].get("bits", quant_bits))
        else:
            cp = max(1, c // ae_ratio[pi])
            q = quant_bits
        enc_fl = 2 * c * cp * h * w * batch
        tc, ec = oh.module_time_energy(enc_fl, enc_fl / 4, dev)
        rows.append((t, e, tc, ec, batch * cp * h * w * q, True))
    t, e = oh.module_time_energy(cum_fl[-1], cum_by[-1], dev)
    rows.append((t, e, 0.0, 0.0, 0.0, True))
    return _finalize(model.name + "-measured", points, rows, device=dev.name)


def llm_decode_split_table(cfg: ModelConfig, ctx_len: int, *,
                           gen_tokens=32, ue_dev=oh.PHONE_NPU, n_points=4,
                           ae_ratio=None, quant_bits=None, kv_bits=None,
                           batch=1) -> SplitPlan:
    """LLM decode offloading: the intermediate feature IS the serving
    state, and its size grows with context length.

    A task serves one request of ``ctx_len`` context tokens plus
    ``gen_tokens`` generated tokens. A split b = k hands the edge
    everything above layer k: the UE prefills layers [0, k) over the
    context, then ships the AE-compressed boundary hidden-state sequence
    (ctx_len x d') PLUS the UE-side layers' serving cache
    (``models.cache.entry_payload_bits`` — KV at ``kv_bits``, sliding
    windows capped, SSM/RG-LRU O(1) state), so the edge can finish the
    prefill at layer k and decode through the full stack without redoing
    the UE's work. ``f_bits`` is therefore a FUNCTION OF CONTEXT LENGTH —
    a fundamentally different overhead curve than CNN features, where the
    payload shrinks with depth.

      b = 0    ship the raw token ids; the edge does everything
      b = k    UE prefills layers [0, k); payload = hiddens + cache
      b = B+1  full local: prefill + gen_tokens decode steps on the UE

    ``kv_bits`` overrides cfg.kv_quant_bits for the SHIPPED cache (0 =
    16-bit; an MLA layer ships its latent unquantized). Opt-in like the
    other measured builders; the default
    ``transformer_split_table`` is untouched."""
    from repro.models.cache import entry_payload_bits

    ctx_len = int(ctx_len)
    if kv_bits is not None:
        cfg = cfg.replace(kv_quant_bits=kv_bits)
    ae_ratio = ae_ratio or cfg.bottleneck_ratio
    quant_bits = quant_bits or cfg.quant_bits
    btypes = cfg.block_types()
    L = len(btypes)
    pre = oh.layer_costs(cfg, ctx_len)
    dec = oh.decode_layer_costs(cfg, ctx_len)
    points = [max(1, round(L * (i + 1) / (n_points + 1)))
              for i in range(n_points)]

    embed_pb = cfg.vocab_size * cfg.d_model * 2
    cum_fl = np.cumsum([l["flops"] for l in pre]) * batch
    cum_by = np.cumsum([l["bytes"] for l in pre]) * batch
    cum_pb = np.cumsum([l["param_bytes"] for l in pre])
    cum_kv = np.cumsum([entry_payload_bits(cfg, bt, batch, ctx_len)
                        for bt in btypes])

    d = cfg.d_model
    dprime = max(1, d // ae_ratio)
    rows = []
    # b = 0: raw token ids
    rows.append((0.0, 0.0, 0.0, 0.0, ctx_len * 32 * batch, True))
    for k in points:
        t, e = oh.module_time_energy(cum_fl[k - 1], cum_by[k - 1], ue_dev)
        enc_fl = 2 * ctx_len * d * dprime * batch
        tc, ec = oh.module_time_energy(enc_fl, enc_fl / 4, ue_dev)
        bits = ctx_len * dprime * quant_bits * batch + cum_kv[k - 1]
        ue_bytes = embed_pb + cum_pb[k - 1] + cum_kv[k - 1] / 8
        rows.append((t, e, tc, ec, bits, ue_bytes <= ue_dev.mem_bytes))
    # b = B+1: full-local prefill + decode (multi-frame on seconds scale)
    emb = oh.embed_costs(cfg, 1)
    dec_fl = sum(l["flops"] for l in dec) * batch + emb["flops"] * batch
    dec_by = sum(l["bytes"] for l in dec) * batch + emb["bytes"]
    t, e = oh.module_time_energy(cum_fl[-1] + gen_tokens * dec_fl,
                                 cum_by[-1] + gen_tokens * dec_by, ue_dev)
    total_pb = embed_pb + cum_pb[-1] + (emb["param_bytes"] - embed_pb)
    ue_bytes = total_pb + cum_kv[-1] / 8
    rows.append((t, e, 0.0, 0.0, 0.0, ue_bytes <= ue_dev.mem_bytes))
    name = f"{cfg.name}-decode-ctx{ctx_len}"
    return _finalize(name, points, rows, device=ue_dev.name)


def measured_split_table(target, **kw) -> SplitPlan:
    """Measured-table dispatcher, mirroring ``split_table``: CNNModel ->
    compiled-HLO-measured table; ModelConfig -> LLM-decode table (pass
    ``ctx_len``)."""
    if isinstance(target, CNNModel):
        return measured_cnn_split_table(target, kw.pop("in_size", 224), **kw)
    return llm_decode_split_table(target, kw.pop("ctx_len", 1024), **kw)
