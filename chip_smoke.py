#!/usr/bin/env python
"""Bring-up check: drive the training, dispatch and edge paths once on a TPU.

  python chip_smoke.py              # one chip: phases (a)-(e) below
  python chip_smoke.py --chips 4    # four chips: the sharded rollout path only

Phases, each a function that takes its sizes as arguments:

  (a) ``check_device``    exit non-zero unless JAX's first device is a TPU
  (b) ``train_phase``     MAHPPO with the entity policy and the fused pair
                          scorer at N=1024 UEs on a 2-server pool; the Pallas
                          scorer is checked against ``kernels.ref`` on a live
                          state
  (c) ``dispatch_phase``  Poisson arrivals through ``StreamSim`` with the
                          trained ``EntityDispatcher``, then an int8
                          ``TrunkDispatcher``; the stream ledger must balance
                          and the fused int8 trunk must match its oracle
  (d) ``edge_phase``      the paper's ResNet18 (101 classes) split at every
                          partitioning point: UE modules, PCA autoencoder,
                          Pallas quantize/dequantize, decoder, edge modules
  (e) ``lowering_phase``  every main-path op lowers to a Mosaic kernel
                          (``tpu_custom_call``), never XLA or interpret mode
  ``sharded_phase``       (``--chips 4``) n_shards=4 training against the
                          n_shards=1 run from the same seed

Any failed check raises and the script exits non-zero. Earlier lines print
set-up facts per phase (wall and compile seconds, peak device bytes, compile
cache hits); the last line is the JSON verdict with the device JAX reports.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.bench_ue_scaling import _cfg, _env  # noqa: E402
from repro.core import cnn, compressor  # noqa: E402
from repro.core.split import cnn_split_table  # noqa: E402
from repro.kernels import ops, quant, ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.optim import adamw_init  # noqa: E402
from repro.rl import nets  # noqa: E402
from repro.rl.distill import quantize_flat_trunk  # noqa: E402
from repro.rl.mahppo import (evaluate_policy, init_agent,  # noqa: E402
                             init_states, make_train_fns, train_mahppo)
from repro.stream.adapter import (EntityDispatcher,  # noqa: E402
                                  TrunkDispatcher, stream_env_state)
from repro.stream.events import StreamParams, StreamSim  # noqa: E402

# the f32 tolerance of tests/test_kernels.py
F32_TOL = 1e-5

class CompileCounter:
    """Seconds in XLA compilation (or loading from the persistent cache),
    and cache hits and misses, from JAX's monitoring events. Tracing is
    left out: a nested jit reports its trace inside its caller's."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def run_phase(name, counter, fn, *args, **kwargs):
    """Run one phase; print its wall and compile seconds and peak bytes."""
    c0, t0 = counter.seconds, time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase {name}: wall_s={wall:.3f} "
          f"compile_s={counter.seconds - c0:.3f} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    return out


# ------------------------------------------------------------------ (a)
def check_device(n_chips):
    """The device JAX reports, as the verdict line names it. Exits
    non-zero when there is no TPU or fewer than ``n_chips`` devices."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print(f"no TPU: JAX's first device is on {dev['platform']!r}",
              file=sys.stderr)
        sys.exit(2)
    if dev["count"] < n_chips:
        print(f"asked for {n_chips} chips, JAX sees {dev['count']}",
              file=sys.stderr)
        sys.exit(2)
    return dev


def _assert_close(name, got, want, tol=F32_TOL):
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    print(f"  {name}: max_abs_diff={err:.3e} (tol {tol:g})", flush=True)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=name)


def _ref_f32(fn, *args):
    """A ``kernels.ref`` oracle at full f32 matmul precision (XLA's TPU
    default multiplies f32 in bf16 passes)."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def fleet_env(n_ue):
    """The giant-fleet env of ``bench_ue_scaling``: ResNet18 split tables,
    N UEs on two channels, the two-server demo pool."""
    return _env(cnn_split_table(cnn.make_resnet18(101), 224), n_ue)


# ------------------------------------------------------------------ (b)
def train_phase(n_ue, iterations, seed):
    """Train the entity policy with the fused scorer on the giant-fleet
    rung of ``bench_ue_scaling``. Returns (env, agent, history)."""
    env = fleet_env(n_ue)
    cfg = dataclasses.replace(_cfg(n_ue), iterations=iterations)
    agent, hist = train_mahppo(env, cfg, seed=seed)
    for h in hist:
        for k in ("reward_mean", "actor_loss", "value_loss"):
            assert np.isfinite(h[k]), (k, h)
    print(f"  {iterations} iterations at N={n_ue}: reward_mean "
          f"{[round(h['reward_mean'], 6) for h in hist]}", flush=True)

    # the Pallas scorer against the naive oracle on one live state
    s = env.reset(jax.random.PRNGKey(seed + 1))
    p = agent["entity_actor"]
    ue = nets.entity_trunk(p, env.observe_entities(s))[0]
    raw = env.observe_entities_raw(s)["raw"]
    logits, srv = jax.jit(ops.pair_scorer)(ue, raw, p["srv_enc"],
                                           p["scorer"])
    want_l, want_s = _ref_f32(
        ref.pair_scorer_ref, ue, raw["d"], raw["work"], raw["active"],
        raw["geom"], raw["consts"], p["srv_enc"]["w"], p["srv_enc"]["b"],
        p["scorer"][0]["w"], p["scorer"][0]["b"], p["scorer"][1]["w"],
        p["scorer"][1]["b"])
    _assert_close("pair_scorer route logits", logits, want_l)
    _assert_close("pair_scorer server embeddings", srv, want_s)
    return env, agent, hist


# ------------------------------------------------------------------ (c)
def dispatch_phase(env, agent, *, rate, horizon, seed, min_tasks):
    """Stream Poisson arrivals through the entity dispatcher and an int8
    flat-trunk dispatcher. Every dispatched task completes, so each must
    complete at least ``min_tasks``."""
    trunk = quantize_flat_trunk(nets.init_flat_trunk(
        jax.random.PRNGKey(seed), env.ue_feat_dim, env.action_space))
    sp = StreamParams(rate=rate, horizon=horizon)
    for name, disp in (("entity", EntityDispatcher(env, agent)),
                       ("trunk_int8", TrunkDispatcher(env, trunk,
                                                      seed=seed))):
        sim = StreamSim(env, disp, sp, seed=seed)
        t0 = time.perf_counter()
        rep = sim.run()
        led = sim.ledger()
        print(f"  {name}: {led} in {time.perf_counter() - t0:.3f} s, "
              f"miss rate {rep['miss_rate']}, p99 sojourn "
              f"{rep['sojourn_p99']} s", flush=True)
        assert led["arrivals"] == (led["completed"] + led["dropped"]
                                   + led["queued"] + led["in_flight"]), led
        assert led["completed"] >= min_tasks, (name, led)

    # the fused int8 trunk against its dequantize-then-matmul oracle, on
    # the observation rows of a live stream state
    sim = StreamSim(env, TrunkDispatcher(env, trunk, seed=seed), sp,
                    seed=seed)
    for _ in range(env.params.n_ue):
        sim.step()
    rows = env.observe_per_ue(stream_env_state(sim))
    ql = trunk["qlayers"]
    got = jax.jit(lambda r: ops.flat_trunk(r, ql, bits=trunk["bits"]))(rows)
    want = _ref_f32(
        lambda r: ref.flat_trunk_ref(
            r, tuple(l["codes"] for l in ql), tuple(l["mn"] for l in ql),
            tuple(l["mx"] for l in ql), tuple(l["b"] for l in ql),
            bits=trunk["bits"]), rows)
    _assert_close("flat_trunk int8 head columns", got, want)


# ------------------------------------------------------------------ (d)
def edge_phase(*, batch, size, ratio, bits, seed):
    """The paper's ResNet18 split at every partitioning point, with the
    PCA autoencoder and Pallas quantize/dequantize on the boundary.
    Returns {split module: top-1 agreement with the unsplit forward}."""
    model = cnn.make_resnet18(101)
    params = model.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (batch, 3, size, size))
    full = jax.jit(lambda p, x: cnn.forward(model, p, x))(params, x)
    top1 = np.argmax(np.asarray(full), -1)
    quantize = jax.jit(ops.quantize, static_argnames="bits")
    dequantize = jax.jit(ops.dequantize, static_argnames="bits")
    agreement = {}
    for k in model.split_after:
        feat = jax.jit(lambda p, x: cnn.forward(model, p, x, upto=k + 1))(
            params, x)
        ae = jax.jit(compressor.pca_init_autoencoder,
                     static_argnums=1)(feat, feat.shape[1] // ratio)
        z = jax.jit(compressor.encode)(ae, feat)
        mn, mx = jnp.min(z), jnp.max(z)
        codes = quantize(z, mn, mx, bits=bits)
        want = jax.jit(quant.quantize_xla, static_argnames="bits")(
            z, mn, mx, bits=bits)
        assert codes.dtype == want.dtype == jnp.uint8, codes.dtype
        n_diff = int(jnp.sum(codes != want))
        assert n_diff == 0, f"split {k}: {n_diff} codes differ from XLA"
        z_hat = dequantize(codes, mn, mx, bits=bits)
        _assert_close(f"split {k} dequantize vs XLA", z_hat,
                      jax.jit(quant.dequantize_xla, static_argnames="bits")(
                          codes, mn, mx, bits=bits), tol=1e-6)
        logits = jax.jit(lambda p, zh: cnn.forward_from(
            model, p, compressor.decode(ae, zh), k + 1))(params, z_hat)
        assert logits.shape == (batch, 101), logits.shape
        assert bool(jnp.all(jnp.isfinite(logits))), f"split {k}"
        agreement[k] = float(np.mean(np.argmax(np.asarray(logits), -1)
                                     == top1))
        print(f"  split after module {k}: boundary {tuple(feat.shape)} -> "
              f"codes {tuple(codes.shape)} uint8, top-1 agreement "
              f"{agreement[k]}", flush=True)
    return agreement


# ------------------------------------------------------------------ (e)
def lowering_phase(*, n_ue=1024, n_servers=2, trunk_rows=10000,
                   boundary=(8, 16, 56, 56), bottleneck=(4096, 2048, 512)):
    """Lower every main-path op through ``kernels.ops`` at main-path
    widths; each compiled program must hold a Mosaic kernel."""
    f32 = jnp.float32
    S = jax.ShapeDtypeStruct
    sc = lambda: S((), f32)
    raw = {"d": S((n_ue,), f32), "work": S((n_ue,), f32),
           "active": S((n_ue,), f32), "geom": S((n_servers, 3), f32),
           "consts": S((8,), f32)}
    srv_enc = {"w": S((4, 32), f32), "b": S((32,), f32)}
    scorer = [{"w": S((163, 48), f32), "b": S((48,), f32)},
              {"w": S((48, 1), f32), "b": S((1,), f32)}]
    dims = (19, 64, 64, 13)
    qlayers = [{"codes": S((a, b), jnp.uint8), "mn": sc(), "mx": sc(),
                "b": S((b,), f32)} for a, b in zip(dims, dims[1:])]
    t, d, dp = bottleneck
    cases = {
        "pair_scorer": (lambda u, r, se, s: ops.pair_scorer(u, r, se, s),
                        (S((n_ue, 128), f32), raw, srv_enc, scorer)),
        "flat_trunk": (lambda x, q: ops.flat_trunk(x, q),
                       (S((trunk_rows, dims[0]), f32), qlayers)),
        "quantize": (lambda x, a, b: ops.quantize(x, a, b),
                     (S(boundary, f32), sc(), sc())),
        "dequantize": (lambda y, a, b: ops.dequantize(y, a, b),
                       (S(boundary, jnp.uint8), sc(), sc())),
        "bottleneck_encode": (lambda x, w, a, b: ops.bottleneck_encode(
            x, w, a, b), (S((t, d), f32), S((d, dp), f32), sc(), sc())),
    }
    for name, (fn, args) in cases.items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, f"{name} did not lower to Mosaic"
        print(f"  {name}: tpu_custom_call present", flush=True)


# ------------------------------------------------------------ four chips
def sharded_phase(*, n_ue, iterations, seed, n_shards, eval_frames):
    """Train with rollouts sharded over ``n_shards`` devices and, from the
    same seed, unsharded through ``train_mahppo``. The sharded run's env
    states must be split over every device, and each trained agent must
    evaluate the same sharded and unsharded."""
    env = fleet_env(n_ue)
    base = dataclasses.replace(_cfg(n_ue), iterations=iterations)
    assert base.n_envs % n_shards == 0, (base.n_envs, n_shards)
    cfg = dataclasses.replace(base, n_shards=n_shards)

    key = jax.random.PRNGKey(seed)
    key, ki, kr = jax.random.split(key, 3)
    agent = init_agent(ki, env, entity_policy=True)
    opt = adamw_init(agent)
    states = init_states(env, cfg, kr)
    iteration = make_train_fns(env, cfg)
    for _ in range(iterations):
        agent, opt, key, states, metrics = iteration(agent, opt, key, states)
        assert np.isfinite(float(metrics["reward_mean"])), metrics
        assert np.isfinite(float(metrics["actor_loss"])), metrics
    shards = sorted((s.device.id, s.data.shape)
                    for s in states.k.addressable_shards)
    print(f"  sharded env state k: {states.k.sharding} shards {shards}",
          flush=True)
    assert len(states.k.sharding.device_set) == n_shards, shards
    assert not states.k.sharding.is_fully_replicated, shards

    agent1, hist1 = train_mahppo(env, base, seed=seed)
    print(f"  reward_mean sharded {float(metrics['reward_mean'])} "
          f"unsharded {hist1[-1]['reward_mean']}", flush=True)
    for name, ag in (("sharded-trained", agent), ("unsharded-trained",
                                                  agent1)):
        res = [evaluate_policy(env, ag, frames=eval_frames, seed=seed,
                               fused_scorer=True, n_envs=n_shards,
                               n_shards=s) for s in (n_shards, 1)]
        print(f"  eval {name}: n_shards={n_shards} {res[0]} | "
              f"n_shards=1 {res[1]}", flush=True)
        for k in ("reward", "t_task", "e_task", "completed"):
            np.testing.assert_allclose(res[0][k], res[1][k], rtol=1e-4,
                                       atol=1e-6, err_msg=f"{name} {k}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded rollout path")
    args = ap.parse_args(argv)
    device = check_device(args.chips)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    if args.chips == 4:
        run_phase("sharded", counter, sharded_phase, n_ue=1024,
                  iterations=3, seed=0, n_shards=4, eval_frames=16)
    else:
        env, agent, _ = run_phase("train", counter, train_phase, 1024, 3, 0)
        run_phase("dispatch", counter, dispatch_phase, env, agent, rate=1.0,
                  horizon=0.4, seed=0, min_tasks=200)
        run_phase("edge", counter, edge_phase, batch=8, size=224, ratio=4,
                  bits=8, seed=0)
        run_phase("lowering", counter, lowering_phase)
    print(f"compile cache: hits={counter.cache_hits} "
          f"misses={counter.cache_misses}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
