"""Quickstart: the paper's pipeline end-to-end on a reduced setup.

1. Build a split plan for an assigned architecture (layer-indivisible tasks,
   AE-compressed boundary features — paper §2-3).
2. Train a MAHPPO scheduler for 5 UEs sharing 2 channels (paper §5).
3. Compare against full-local inference (paper §6).

  PYTHONPATH=src python examples/quickstart.py [--arch qwen3-1.7b]
"""
import argparse

import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.split import transformer_split_table
from repro.env.mecenv import MECEnv, make_env_params
from repro.launch.cache import enable_compile_cache
from repro.rl.baselines import local_policy_eval
from repro.rl.mahppo import MAHPPOConfig, evaluate_policy, train_mahppo


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=ARCH_IDS)
    ap.add_argument("--iterations", type=int, default=30)
    ap.add_argument("--n-ue", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    plan = transformer_split_table(cfg)
    print(f"split plan for {args.arch}:")
    for b in range(plan.n_actions):
        print(f"  b={b}: t_local={1e3*plan.t_local[b]:8.1f}ms "
              f"payload={plan.f_bits[b]/1e3:9.1f}kbit "
              f"feasible={bool(plan.feasible[b])}")

    t_full = float(plan.t_local[-1])
    e_full = float(plan.e_local[-1])
    env = MECEnv(make_env_params(
        plan, n_ue=args.n_ue, n_channels=2,
        t0=max(0.5, round(10 * t_full, 1)),
        beta=t_full / max(e_full, 1e-9)))

    print(f"\ntraining MAHPPO ({args.iterations} iterations)...")
    ppo = MAHPPOConfig(iterations=args.iterations, horizon=1024, n_envs=8)
    agent, hist = train_mahppo(env, ppo, seed=0,
                               log_cb=lambda r: print(
                                   f"  iter {r['iteration']:3d} "
                                   f"reward={r['reward_mean']:.4f}")
                               if r["iteration"] % 5 == 0 else None)

    ev = evaluate_policy(env, agent, frames=64)
    lo = local_policy_eval(env, frames=64)
    beta = float(env.params.beta)
    ovh = ev["t_task"] + beta * ev["e_task"]
    lovh = lo["t_task"] + beta * lo["e_task"]
    print(f"\nMAHPPO : latency {1e3*ev['t_task']:.1f} ms  "
          f"energy {1e3*ev['e_task']:.1f} mJ  overhead {ovh:.4f}")
    print(f"Local  : latency {1e3*lo['t_task']:.1f} ms  "
          f"energy {1e3*lo['e_task']:.1f} mJ  overhead {lovh:.4f}")
    print(f"overhead reduction: {100*(1-ovh/lovh):.0f}%")


if __name__ == "__main__":
    main()
