"""End-to-end collaborative inference driver: ACTUALLY runs the split model.

A reduced assigned architecture is decoupled at the MAHPPO-chosen split
point: the "UE" runs the front layers and the AE+quantization compressor
(the Pallas kernel path), bits cross a simulated wireless channel, the
"edge" dequantizes, decodes and finishes the forward pass. Verifies that
end-to-end top-1 predictions survive compression, and reports simulated
latency per request batch.

  PYTHONPATH=src python examples/collaborative_serve.py --arch qwen3-1.7b

With ``--fleet`` it instead schedules a HETEROGENEOUS 4-UE fleet (two
ResNet18 CNN UEs on Jetson-class devices — one degraded to an IoT-class
SoC — plus two reduced-transformer UEs on phone NPUs) with MAHPPO over the
per-UE split tables, and prints each UE's learned split decision:

  PYTHONPATH=src python examples/collaborative_serve.py --fleet

With ``--servers E`` the edge side becomes a POOL of E servers (TPU-v5e
near the cell center, weaker/farther tiers behind it): the action space
grows a `route` head, and the demo prints each UE's learned (split,
server) decision plus the fleet's load distribution vs the
nearest-server baseline:

  PYTHONPATH=src python examples/collaborative_serve.py --servers 2

With ``--shared-policy`` the N per-UE actors are replaced by ONE
weight-shared actor applied to every UE's featurized observation row
(``env.observe_per_ue``) — O(1) parameters in the fleet size, and the
trained agent evaluates zero-shot on other fleet sizes and pool layouts
(see ``benchmarks/bench_generalization.py``). Composes with --churn and
--servers:

  PYTHONPATH=src python examples/collaborative_serve.py --shared-policy \\
      --servers 2

With ``--entity-policy`` the policy consumes the structured ENTITY-SET
observation (``env.observe_entities``: per-UE rows, per-server rows, and
UE x server edge features) and scores every (UE, server) pair with one
shared route scorer. Training resamples the pool geometry every episode
(the route head actually learns to read the pool), and the SAME
parameters then run zero-shot on a pool of a different SIZE — the demo
finishes by dropping the trained agent onto an E+1-server pool:

  PYTHONPATH=src python examples/collaborative_serve.py --entity-policy \\
      --servers 2

With ``--llm`` the fleet is the MIXED CNN + LLM-decode scenario of
``benchmarks/bench_llm_offload.py``: two ResNet18 UEs plus one
qwen3-1.7b decode UE per context rung (256 / 1024 / 4096), whose
boundary payload (compressed hidden states + UE-side KV cache) GROWS
with context, against a thin multi-tenant v5e slice + edge-GPU pool.
The demo prints each rung's learned split and whether the
context-length-dependent shift (short rungs offload, the long rung
stays local) has emerged:

  PYTHONPATH=src python examples/collaborative_serve.py --llm

With ``--distill`` the demo closes the train-big/serve-small loop: the
trained entity teacher is distilled into a small flat-trunk student on
the STATIC deployment pool (``rl.distill`` — one fused MLP pass over
``observe_per_ue`` rows emits every action head), the student is int8
weight-quantized for the fused dequant-matmul kernel, and the demo
finishes with a batch-1 dispatch-latency readout (teacher vs distilled
f32 vs int8):

  PYTHONPATH=src python examples/collaborative_serve.py --distill
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import reduced
from repro.core.compressor import init_autoencoder
from repro.core.split import transformer_split_table
from repro.env.channel import channel_gain, uplink_rates
from repro.kernels import ops as kops
from repro.launch.cache import enable_compile_cache
from repro.models import apply_model, init_params
from repro.models.layers import apply_norm
from repro.models.model import _logits, _run_stack, layer_plan


def run_split_forward(params, cfg, tokens, split_layer, ae, bits=8):
    """UE part -> compress -> (channel) -> decompress -> edge part."""
    lead, pattern, n_groups, tail_types = layer_plan(cfg)
    assert len(pattern) == 1 and not lead, \
        "example uses uniform-pattern archs"
    bt = pattern[0]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = jnp.take(params["embed"], tokens, axis=0)

    stack = params["decoder"]
    blocks = stack["blocks"][0]

    def run_layers(x, lo, hi):
        from repro.models.blocks import apply_block
        for i in range(lo, hi):
            p_i = jax.tree_util.tree_map(lambda a: a[i], blocks)
            x, _, _ = apply_block(p_i, x, cfg, bt, positions=positions,
                                  mode="train")
        return x

    # ---- UE side
    x = run_layers(x, 0, split_layer)
    mn, mx = float(x.min()), float(x.max())
    codes = kops.bottleneck_encode(x.astype(jnp.float32),
                                   ae["enc"].astype(jnp.float32), mn, mx,
                                   bits=bits)
    payload_bits = codes.size * bits

    # ---- edge side
    z = kops.dequantize(codes, mn, mx, bits=bits)
    x_hat = (z @ ae["dec"]).astype(x.dtype)
    x = run_layers(x_hat, split_layer, cfg.n_layers)
    x = apply_norm(stack["ln_f"], x, cfg)
    return _logits(params, cfg, x), payload_bits


def run_fleet_demo(arch: str, iterations: int, churn_rate=0.0,
                   leave_rate=0.0, n_servers=1, shared_policy=False,
                   entity_policy=False, n_ue=4, fused_scorer=False,
                   n_shards=1, llm=False, distill=False):
    """Mixed-fleet scheduling: per-UE split tables + device tiers end-to-end
    through MAHPPO, vs the non-coordinating greedy heuristic. With nonzero
    churn/leave rates the fleet is DYNAMIC: UEs join from a standby pool and
    drop mid-episode, and the policy schedules whoever is present. With
    n_servers > 1 the edge side is an EdgePool and routing is part of the
    learned action. With shared_policy, ONE weight-shared actor over per-UE
    feature rows (`env.observe_per_ue`) replaces the N per-UE actors —
    O(1) parameters in the fleet size, and the trained agent transfers
    zero-shot to other fleet sizes (see benchmarks/bench_generalization.py)."""
    from repro.core.fleets import (EdgePool, LLM_CTX_RUNGS, make_edge_pool,
                                   make_llm_mixed_fleet, make_mixed_fleet,
                                   random_pool_ranges)
    from repro.env.mecenv import MECEnv, make_env_params
    from repro.rl import nets
    from repro.rl.heuristics import greedy_eval
    from repro.rl.mahppo import MAHPPOConfig, evaluate_policy, train_mahppo

    t0 = 0.5
    if llm:
        # the bench_llm_offload scenario: CNN UEs + one LLM-decode UE per
        # context rung, against a thin multi-tenant v5e slice and an
        # interference-free edge-GPU tier; long frames (t0 = 2 s) so the
        # ctx-4096 rung's full-local run spans multiple frames
        from repro.core import overhead as oh_
        fleet = make_llm_mixed_fleet(arch)
        t0 = 2.0
        print(f"LLM context rungs: {LLM_CTX_RUNGS} (f_bits grows with "
              f"context — KV cache rides the boundary payload)")
    else:
        fleet = make_mixed_fleet(arch, n_ue=n_ue)
    print("fleet:")
    for i, (name, prof) in enumerate(zip(fleet.names, fleet.profiles)):
        feas = int(fleet.feasible[i].sum())
        print(f"  ue{i}: {name:14s} on {prof.name:12s} "
              f"(P_compute={prof.p_compute:.1f} W, "
              f"{feas}/{fleet.n_actions} feasible actions)")
    if llm:
        pool = EdgePool((
            oh_.ServerProfile.from_device(oh_.TPU_V5E, utilization=0.025),
            oh_.ServerProfile.from_device(oh_.EDGE_GPU, dist_scale=1.4)))
    else:
        pool = make_edge_pool(n_servers) if n_servers > 1 else None
    if pool is not None:
        print("edge pool:")
        for e, srv in enumerate(pool.servers):
            print(f"  srv{e}: {srv.name:10s} dist x{srv.dist_scale:.1f}  "
                  f"bw x{srv.bw_scale:.1f}  "
                  f"edge_speed={srv.edge_speed/1e12:.1f} TFLOP/s")

    randomize = entity_policy and pool is not None and not llm
    env = MECEnv(make_env_params(
        fleet, n_channels=2, t0=t0, churn_rate=churn_rate,
        leave_rate=leave_rate, pool=pool,
        pool_ranges=random_pool_ranges(pool.n_servers) if randomize
        else None))
    print(f"action space: {', '.join(env.action_space.names)}")
    demo_active = None         # representative membership for the baselines
    if env.dynamic:
        print(f"dynamic fleet: join intensity {churn_rate}, "
              f"leave prob {leave_rate}/frame")
        # short random rollout to show membership actually churns
        s = env.reset(jax.random.PRNGKey(7))
        trace = []
        demo_active = np.asarray(s.active)
        for t in range(24):
            n = env.params.n_ue
            acts = {"split": jnp.full((n,), env.n_actions_b - 1, jnp.int32),
                    "channel": jnp.zeros((n,), jnp.int32),
                    "power": jnp.full((n,), 0.05)}
            if env.multi_server:
                acts["route"] = jnp.zeros((n,), jnp.int32)
            s, _, done, info = env.step(s, acts)
            if bool(done):
                break               # post-done state is the auto-reset fleet
            trace.append("".join("#" if a else "." for a in
                                 np.asarray(s.active)))
            if np.asarray(s.active).any():
                demo_active = np.asarray(s.active)  # last non-empty snapshot
        print("  membership (one column per UE, # active / . standby):")
        for t, row in enumerate(trace):
            if t % 4 == 0:
                print(f"    frame {t:2d}: {row}")
    mode = "entity-set actor, per-server route scorer" if entity_policy \
        else "weight-shared actor" if shared_policy else "per-UE actors"
    extra = " over randomized pool geometries" if randomize else ""
    print(f"\ntraining MAHPPO ({mode}) on the mixed fleet{extra} "
          f"({iterations} iterations)...")
    if fused_scorer:
        print("  fused pair-scorer kernel path (observe_entities_raw)")
    if n_shards > 1:
        print(f"  rollouts sharded over {n_shards} devices "
              f"({len(jax.devices())} visible)")
    cfg = MAHPPOConfig(iterations=iterations, horizon=512, n_envs=4,
                       reuse=4, shared_policy=shared_policy,
                       entity_policy=entity_policy,
                       randomize_pool=randomize,
                       fused_scorer=fused_scorer, n_shards=n_shards)
    agent, hist = train_mahppo(env, cfg, seed=0,
                               log_cb=lambda r: print(
                                   f"  iter {r['iteration']:3d} "
                                   f"reward={r['reward_mean']:.4f}")
                               if r["iteration"] % 5 == 0 else None)
    ev = evaluate_policy(env, agent, frames=64)
    # score greedy on a comparable fleet: the traced membership snapshot,
    # so both columns describe a churned fleet, not all-N vs active-only
    gr = greedy_eval(env, active=demo_active)
    beta = float(env.params.beta)
    if env.dynamic:
        print(f"\nmean fleet size over eval: {ev['n_active']:.2f} "
              f"of {env.params.n_ue} UEs"
              + ("" if demo_active is None else
                 f"; greedy scored on {int(demo_active.sum())} active UEs"))
    print(f"\nMAHPPO : latency {1e3*ev['t_task']:.1f} ms  "
          f"energy {1e3*ev['e_task']:.1f} mJ  "
          f"overhead {ev['t_task'] + beta*ev['e_task']:.4f}")
    print(f"greedy : latency {1e3*gr['t_task']:.1f} ms  "
          f"energy {1e3*gr['e_task']:.1f} mJ  "
          f"overhead {gr['overhead']:.4f}  (per-UE b={gr['b']}"
          + (f", route={gr['route']}" if "route" in gr else "") + ")")
    if env.multi_server:
        from repro.rl.baselines import load_aware_eval, nearest_server_eval
        near = nearest_server_eval(env, active=demo_active)
        load = load_aware_eval(env, active=demo_active)
        print(f"nearest: overhead {near['overhead']:.4f}  "
              f"(route={near['route']})")
        print(f"loadbal: overhead {load['overhead']:.4f}  "
              f"(route={load['route']})")

    if (shared_policy or entity_policy) and n_ue <= 16:
        # (skipped at giant N: instantiating N per-UE actors just for the
        # comparison means N obs_dim-sized orthogonal inits)
        from repro.rl.mahppo import init_agent
        n_pol = nets.param_count(agent.get("actor")
                                 or agent["entity_actor"])
        n_per_ue = nets.param_count(
            init_agent(jax.random.PRNGKey(0), env)["actors"])
        kind = "entity" if entity_policy else "shared"
        print(f"\nactor parameters: {n_pol} {kind} (O(1) in fleet size"
              + (" AND pool size" if entity_policy else "")
              + f") vs {n_per_ue} for per-UE actors at N="
              f"{env.params.n_ue}")

    # learned per-UE decisions at the eval state
    from repro.rl.mahppo import _policy_all
    space = env.action_space
    s = env.reset(jax.random.PRNGKey(0), eval_mode=True)
    masks = env.action_masks()
    if entity_policy:
        dist = nets.entity_actor_forward(
            agent["entity_actor"], space, env.observe_entities(s),
            space.broadcast_masks(masks, env.params.n_ue))
    elif shared_policy:
        dist = nets.shared_actor_forward(
            agent["actor"], space, env.observe_per_ue(s),
            space.broadcast_masks(masks, env.params.n_ue))
    else:
        dist = _policy_all(agent["actors"], space, env.observe(s), masks)
    a_star = jax.vmap(space.mode)(dist, masks)
    for i, b in enumerate(np.asarray(a_star["split"])):
        kind = ("raw offload" if b == 0 else
                "full local" if b == env.n_actions_b - 1 else f"split b={b}")
        where = f" -> srv{int(a_star['route'][i])}" \
            if env.multi_server and b != env.n_actions_b - 1 else ""
        print(f"  ue{i} ({fleet.names[i]}): {kind}{where}")
    if env.multi_server:
        counts = np.bincount(np.asarray(a_star["route"]),
                             minlength=env.n_servers)
        print(f"  learned route distribution: "
              + ", ".join(f"srv{e}={int(c)}" for e, c in enumerate(counts)))
    if llm:
        b_llm = np.asarray(a_star["split"])[-len(LLM_CTX_RUNGS):]
        local = env.n_actions_b - 1
        offl = b_llm[:-1][b_llm[:-1] != local]
        shift = offl.size > 0 and (b_llm[-1] == local
                                   or b_llm[-1] > offl.min())
        print(f"  context-length shift (short rungs offload, "
              f"ctx{LLM_CTX_RUNGS[-1]} stays local/later): "
              f"{'YES' if shift else 'not yet at this budget'}")

    # entity policies transfer across pool SIZE: drop the identical
    # parameters onto an E+1-server pool, zero-shot
    if entity_policy and env.multi_server and n_servers < 3 and not llm:
        from repro.rl.baselines import nearest_server_eval
        env_big = MECEnv(make_env_params(
            fleet, n_channels=2, pool=make_edge_pool(n_servers + 1)))
        ev_big = evaluate_policy(env_big, agent, frames=64)
        near_big = nearest_server_eval(env_big)
        ovh_big = ev_big["t_task"] + beta * ev_big["e_task"]
        print(f"\nzero-shot on an UNSEEN {n_servers + 1}-server pool "
              f"(route head is E-free): entity overhead {ovh_big:.4f} vs "
              f"nearest-server {near_big['overhead']:.4f} "
              f"[{'BEATS' if ovh_big <= near_big['overhead'] else 'LOSES'}]")

    if distill:
        # train big, serve small: the entity teacher generalizes across
        # fleets/pools; the deployment serves ONE pool, where a distilled
        # flat trunk prices a dispatch in microseconds
        import time

        from repro.rl.distill import (DistillConfig, distill_entity_policy,
                                      quantize_flat_trunk)
        env_d = env if not randomize else MECEnv(make_env_params(
            fleet, n_channels=2, t0=t0, pool=pool))   # the STATIC pool
        print("\ndistilling into the serve-small flat trunk "
              "(rl.distill; fixed fleet, fixed pool)...")
        student, _ = distill_entity_policy(
            env_d, agent, DistillConfig(iterations=2, frames=48, epochs=120),
            seed=1, log_cb=lambda r: print(
                f"  round {r['iteration']}: dataset {r['states']} states  "
                f"loss {r['loss']:.4f}  mode agreement {r['agreement']:.2f}"))
        qstudent = quantize_flat_trunk(student)
        n_t, n_s = (nets.param_count(agent["entity_actor"]),
                    nets.param_count(student))
        print(f"  teacher {n_t} params "
              f"({nets.param_bytes(agent['entity_actor']) / 1e3:.1f} kB) -> "
              f"student {n_s} ({100 * n_s / n_t:.1f}%); int8 serving "
              f"weights {nets.param_bytes(qstudent) / 1e3:.1f} kB vs "
              f"f32 {nets.param_bytes(student) / 1e3:.1f} kB")
        ev_t = evaluate_policy(env_d, agent, frames=64)
        ev_q = evaluate_policy(env_d, {"flat_trunk": qstudent}, frames=64)
        ovh_t = ev_t["t_task"] + beta * ev_t["e_task"]
        ovh_q = ev_q["t_task"] + beta * ev_q["e_task"]
        print(f"  int8 student overhead {ovh_q:.4f} vs teacher {ovh_t:.4f} "
              f"(ratio {ovh_q / ovh_t:.2f})")

        # the closing readout: one batch-1 policy forward — the per-task
        # cost the dispatcher pays on the streaming hot path (the full
        # batch sweep lives in benchmarks/bench_policy_latency.py)
        space_d = env_d.action_space
        s0 = env_d.reset(jax.random.PRNGKey(0), eval_mode=True)
        masks_d = space_d.broadcast_masks(env_d.action_masks(),
                                          env_d.params.n_ue)
        rows = env_d.observe_per_ue(s0)
        ents = env_d.observe_entities(s0)
        cells = (
            ("entity teacher", jax.jit(lambda: nets.entity_actor_forward(
                agent["entity_actor"], space_d, ents, masks_d))),
            ("distilled f32", jax.jit(lambda: nets.flat_trunk_forward(
                student, space_d, rows, masks_d))),
            ("distilled int8", jax.jit(lambda: nets.flat_trunk_forward(
                qstudent, space_d, rows, masks_d))),
        )

        def best_us(fn, k=20):
            jax.block_until_ready(fn())             # compile + warm
            best = float("inf")
            for _ in range(k):
                t1 = time.perf_counter()
                jax.block_until_ready(fn())
                best = min(best, time.perf_counter() - t1)
            return best * 1e6

        print("  batch-1 dispatch forward (best of 20):")
        for name, fn in cells:
            print(f"    {name:14s}: {best_us(fn):8.1f} us")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=[a for a in ARCH_IDS])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--ratio", type=int, default=4)
    ap.add_argument("--fleet", action="store_true",
                    help="schedule a heterogeneous 4-UE fleet instead of "
                         "running the single-UE split forward")
    ap.add_argument("--churn", action="store_true",
                    help="make the --fleet scenario dynamic: UEs join/leave "
                         "mid-episode (implies --fleet; also implied by "
                         "passing --churn-rate/--leave-rate)")
    ap.add_argument("--churn-rate", type=float, default=None,
                    help="Poisson join intensity per standby slot per frame "
                         "(default 0.2 when churning; implies --churn)")
    ap.add_argument("--leave-rate", type=float, default=None,
                    help="per-frame departure probability of an active UE "
                         "(default 0.1 when churning; implies --churn)")
    ap.add_argument("--servers", type=int, default=1, metavar="E",
                    help="size of the edge pool (E > 1 adds a learned "
                         "`route` action head; implies --fleet)")
    ap.add_argument("--shared-policy", action="store_true",
                    help="train ONE weight-shared actor over per-UE "
                         "feature rows instead of per-UE actors — O(1) "
                         "parameters in the fleet size, transfers "
                         "zero-shot across fleets (implies --fleet)")
    ap.add_argument("--entity-policy", action="store_true",
                    help="train the entity-set policy: structured "
                         "{ue, server, edge} observations through a "
                         "shared per-server route scorer, with the pool "
                         "geometry resampled every episode — transfers "
                         "zero-shot across pool layouts AND sizes "
                         "(implies --fleet; defaults --servers to 2)")
    ap.add_argument("--n-ue", type=int, default=4, metavar="N",
                    help="fleet size: cycles the 4-UE device mix to N "
                         "UEs (the entity policy stays O(1) params in N "
                         "— try 256; implies --fleet)")
    ap.add_argument("--fused-scorer", action="store_true",
                    help="route the entity pair scorer through the fused "
                         "kernel path (kernels.ops.pair_scorer; implies "
                         "--entity-policy) — same logits, no (N, E, .) "
                         "intermediates, the giant-fleet hot path")
    ap.add_argument("--llm", action="store_true",
                    help="schedule the mixed CNN + LLM-decode fleet (one "
                         "UE per context rung; KV cache rides the "
                         "boundary payload) on the bench_llm_offload "
                         "pool — implies --entity-policy")
    ap.add_argument("--distill", action="store_true",
                    help="after training, distill the entity teacher into "
                         "the serve-small flat trunk (rl.distill), int8-"
                         "quantize it for the fused dequant-matmul kernel, "
                         "and close with a batch-1 dispatch-latency "
                         "readout (implies --entity-policy; needs a "
                         "static fleet, so excludes --churn)")
    ap.add_argument("--n-shards", type=int, default=1, metavar="K",
                    help="shard rollout collection over K devices (on "
                         "CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=K before launch; implies --fleet)")
    ap.add_argument("--iterations", type=int, default=15)
    args = ap.parse_args()
    enable_compile_cache()

    if args.entity_policy and args.shared_policy:
        ap.error("pick one of --entity-policy / --shared-policy")
    if args.fused_scorer and args.shared_policy:
        ap.error("--fused-scorer fuses the entity route scorer; it "
                 "cannot combine with --shared-policy")
    if args.fused_scorer:
        args.entity_policy = True
    if args.llm:
        args.entity_policy = True   # the scenario is about routing
    if args.distill:
        args.entity_policy = True   # distillation needs an entity teacher
    if args.entity_policy and args.servers < 2:
        args.servers = 2       # the route scorer needs a pool to score
    churn = (args.churn or args.churn_rate is not None
             or args.leave_rate is not None)
    if args.distill and churn:
        ap.error("--distill targets a fixed deployment fleet; it cannot "
                 "combine with --churn")
    if args.fleet or churn or args.servers > 1 or args.shared_policy \
            or args.entity_policy or args.n_ue != 4 or args.n_shards > 1 \
            or args.llm:
        run_fleet_demo(
            args.arch, args.iterations,
            churn_rate=(0.2 if args.churn_rate is None
                        else args.churn_rate) if churn else 0.0,
            leave_rate=(0.1 if args.leave_rate is None
                        else args.leave_rate) if churn else 0.0,
            n_servers=args.servers, shared_policy=args.shared_policy,
            entity_policy=args.entity_policy, n_ue=args.n_ue,
            fused_scorer=args.fused_scorer, n_shards=args.n_shards,
            llm=args.llm, distill=args.distill)
        return

    cfg = reduced(get_config(args.arch), n_layers=4)
    if len(cfg.block_pattern) != 1:
        cfg = cfg.replace(block_pattern=("dense",))
    params = init_params(cfg, jax.random.PRNGKey(0))

    # The paper assumes a PRE-TRAINED backbone (feature anisotropy is what
    # the AE exploits) — pre-train briefly on the synthetic corpus.
    from repro.data.synthetic import TokenPipelineConfig, token_batch_stream
    from repro.launch.steps import make_train_step
    print("pre-training backbone (150 steps)...")
    train_step, opt_init = make_train_step(cfg, base_lr=3e-3, warmup=20,
                                           total=150)
    opt = opt_init(params)
    stream = token_batch_stream(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch=16))
    sfn = jax.jit(train_step)
    for i in range(150):
        params, opt, m = sfn(params, opt, next(stream))
    print(f"  final train loss {float(m['loss']):.3f}")
    tokens = next(stream)["tokens"][: args.batch]

    ref_logits, _, _ = apply_model(params, cfg, tokens, mode="train")
    ref_top1 = jnp.argmax(ref_logits, -1)

    d = cfg.d_model
    split = cfg.n_layers // 2

    # Fit the optimal LINEAR autoencoder in closed form (PCA of the boundary
    # features on a calibration batch) — the train-free analogue of the
    # paper's stage-1 L2 objective for a 1x1-conv AE.
    calib = jax.random.randint(jax.random.PRNGKey(9), (8, args.seq), 0,
                               cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(args.seq, dtype=jnp.int32),
                                 (8, args.seq))
    from repro.models.blocks import apply_block
    xc = jnp.take(params["embed"], calib, axis=0)
    blocks = params["decoder"]["blocks"][0]
    for i in range(split):
        p_i = jax.tree_util.tree_map(lambda a: a[i], blocks)
        xc, _, _ = apply_block(p_i, xc, cfg, cfg.block_pattern[0],
                               positions=positions, mode="train")
    feats = xc.reshape(-1, d).astype(jnp.float32)
    mu = feats.mean(0)
    _, _, vt = jnp.linalg.svd(feats - mu, full_matrices=False)
    pcs = vt[: d // args.ratio].T                       # (d, d')
    ae = {"enc": pcs, "dec": pcs.T}
    logits, payload_bits = run_split_forward(params, cfg, tokens, split, ae)
    agree = float(jnp.mean((jnp.argmax(logits, -1) == ref_top1)))

    # simulated channel: single UE, 50 m, 0.3 W
    g = channel_gain(jnp.array([50.0]))
    r = uplink_rates(jnp.array([0.3]), jnp.array([0]), g, jnp.array([True]),
                     omega=jnp.array([1e6]), sigma=jnp.array([1e-9]))
    t_tx = payload_bits / float(r[0])
    raw_bits = tokens.size * 32

    print(f"arch={args.arch} (reduced {cfg.n_layers}L d={cfg.d_model}), "
          f"split after layer {split}")
    print(f"boundary payload: {payload_bits/1e3:.1f} kbit "
          f"(hidden f32 would be {tokens.size*d*32/1e3:.0f} kbit, "
          f"rate R={tokens.size*d*32/payload_bits:.0f}x)")
    print(f"uplink {float(r[0])/1e6:.1f} Mb/s -> tx {1e3*t_tx:.1f} ms")
    print(f"top-1 agreement with uncompressed forward: {100*agree:.1f}% "
          f"(PCA linear AE, ratio {args.ratio}x + int8)")
    print(f"raw-input offload would be {raw_bits/1e3:.1f} kbit")


if __name__ == "__main__":
    main()
