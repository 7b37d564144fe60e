"""Streaming serve demo: the trained entity policy as a live dispatcher.

Trains the pool-generalist entity policy on the frame-synchronous MEC env
(randomized 2-server geometries, exactly like the generalization bench),
streaming-fine-tunes it by DAgger distillation of the occupancy-aware
dispatch oracle (``rl.streaming`` — the frame-trained weights transfer
honestly but poorly: the mean-overhead equilibrium picks conservative
power/splits that miss deadlines under load), then deploys it as the
dispatcher of the event-driven asyncio serve daemon
(``repro.stream.dispatcher``): mock UE coroutines generate Poisson task
arrivals with per-class deadlines, the daemon renders the live
queue/occupancy state as an ``EnvState`` and asks the policy where to
split, which server to use and at what power (sampled — the
load-spreading deployment mode — with the channel picked least-loaded at
dispatch time, the same live peek every baseline gets), and mock server
coroutines execute each task for its Eq. 7/8 closed-form service time.
Ends with the QoS report (throughput, deadline-miss rate, p50/p95/p99
sojourn) for the tuned policy, its zero-shot (untuned) form, and the
nearest-server / full-local baselines, all on the SAME arrival
realization.

Everything is deterministic in ``--seed``: the daemon runs on a virtual
clock ((time, seq)-ordered events, per-UE RNG streams), so two runs with
the same seed print byte-identical reports regardless of machine or
scheduler jitter.

  PYTHONPATH=src python examples/streaming_serve.py --seed 0
  # quick look (~1 min, undertrained dispatcher):
  PYTHONPATH=src python examples/streaming_serve.py --iters 10 --tune-iters 4
"""
import argparse

from repro.core.fleets import (make_edge_pool, make_mixed_fleet,
                               random_pool_ranges)
from repro.env.mecenv import MECEnv, make_env_params
from repro.launch.cache import enable_compile_cache
from repro.rl.mahppo import MAHPPOConfig, train_mahppo
from repro.rl.streaming import StreamTuneConfig, finetune_streaming
from repro.stream.adapter import (EntityDispatcher, LocalDispatcher,
                                  NearestServerDispatcher)
from repro.stream.dispatcher import run_daemon
from repro.stream.events import StreamParams


def build_env(n_ue, n_servers, randomized=False):
    pool = make_edge_pool(n_servers)
    ranges = random_pool_ranges(n_servers) if randomized else None
    return MECEnv(make_env_params(make_mixed_fleet(n_ue=n_ue),
                                  n_channels=2, pool=pool,
                                  pool_ranges=ranges))


def print_report(name, rep):
    print(f"  {name:16s} throughput={rep['throughput']:6.1f}/s  "
          f"miss={rep['miss_rate']:6.1%}  drop={rep['drop_rate']:6.1%}  "
          f"sojourn p50={rep['sojourn_p50']:.3f}s "
          f"p95={rep['sojourn_p95']:.3f}s p99={rep['sojourn_p99']:.3f}s")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds training AND the stream (deterministic)")
    ap.add_argument("--ues", type=int, default=8)
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="per-UE mean arrivals / second")
    ap.add_argument("--horizon", type=float, default=10.0,
                    help="seconds of arrivals (the daemon then drains)")
    ap.add_argument("--iters", type=int, default=30,
                    help="MAHPPO training iterations (frame env)")
    ap.add_argument("--tune-iters", type=int, default=14,
                    help="streaming DAgger fine-tune iterations "
                         "(0 = deploy zero-shot)")
    args = ap.parse_args()
    enable_compile_cache()

    print(f"training the entity policy: {args.iters} MAHPPO iterations on "
          f"the frame env (N={args.ues}, randomized "
          f"{args.servers}-server geometries) ...")
    env_rnd = build_env(args.ues, args.servers, randomized=True)
    cfg = MAHPPOConfig(iterations=args.iters, horizon=512, n_envs=4,
                       reuse=4, entity_policy=True, randomize_pool=True)
    agent, hist = train_mahppo(env_rnd, cfg, seed=args.seed)
    print(f"  final frame reward: {hist[-1]['reward_mean']:.4f}")

    env = build_env(args.ues, args.servers)
    tuned = agent
    if args.tune_iters:
        print(f"\nstreaming fine-tune: {args.tune_iters} DAgger iterations "
              "distilling the occupancy-aware dispatch oracle (mid-load + "
              "saturated scenarios) ...")
        tuned, th = finetune_streaming(
            env, agent,
            [StreamParams(rate=6.0, horizon=8.0),
             StreamParams(rate=14.0, horizon=8.0)],
            StreamTuneConfig(iterations=args.tune_iters),
            seed=args.seed + 100,
            log_cb=lambda h: print(
                f"  iter {h['iteration']:2d}: reward="
                f"{h['reward_mean']:8.3f}  miss={h['miss_rate']:6.1%}  "
                f"p99={h['p99']:.3f}s"))

    sp = StreamParams(rate=args.rate, horizon=args.horizon)
    print(f"\nstreaming {args.horizon:.0f}s of Poisson arrivals at "
          f"{args.rate:g} tasks/s/UE through the asyncio daemon "
          f"(seed {args.seed}):")

    log = []
    rep, core = run_daemon(
        env,
        EntityDispatcher(env, tuned, deterministic=False, live_channel=True,
                         seed=args.seed),
        sp, seed=args.seed, server_log=log)
    per_server = [sum(1 for (_, e, _) in log if e == s)
                  for s in range(env.n_servers)]
    print_report("entity (tuned)", rep)
    print(f"    server task counts: {per_server}  "
          f"(tasks={rep['tasks']}, arrivals={rep['arrivals']})")

    for name, disp in [("entity zero-shot", EntityDispatcher(env, agent)),
                       ("nearest-server", NearestServerDispatcher(env)),
                       ("full-local", LocalDispatcher(env))]:
        bre, _ = run_daemon(env, disp, sp, seed=args.seed)
        print_report(name, bre)


if __name__ == "__main__":
    main()
