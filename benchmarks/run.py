"""Benchmark harness entrypoint — one section per paper table/figure plus
the roofline analysis. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--full|--smoke] [--only SECTION]

Sections that guard a jitted-iteration parity ratio (hetero, churn,
multi_server, generalization) report it into a shared ledger; any ratio
above its limit makes the run EXIT NONZERO with a summary line, so CI
catches hot-path regressions instead of scrolling past them. ``--smoke``
runs the RL sections at tiny iteration counts (CI-sized) and still emits
the standardized ``artifacts/BENCH_multi_server.json``,
``artifacts/BENCH_generalization.json``, ``artifacts/BENCH_entity.json``,
``artifacts/BENCH_ue_scaling.json``, ``artifacts/BENCH_streaming.json``,
``artifacts/BENCH_compression.json``,
``artifacts/BENCH_llm_offload.json`` and
``artifacts/BENCH_policy_latency.json`` artifacts. The policy_latency
ledger enforces the train-big/serve-small story: the distilled trunk
within 5% of its entity teacher's mean overhead on the deployment pool,
distilled batch-1 forward at most 0.5x the teacher's µs, int8 fused
kernel parity vs the ``kernels/ref.py`` oracle, the trunk dispatcher
p99 at most nearest-server's at mid-load streaming, and student params
at most 25% of the teacher's (parity/params gated in smoke too). The ue_scaling ledger enforces the giant-fleet story: per-UE
jitted iteration cost at N=256 at most 0.5x the N=16 per-UE cost, and
the fused pair-scorer kernel beating its naive reference on call_us at
N>=256 while matching it numerically. The generalization ledger also
enforces the zero-shot WINS: shared/greedy at n8/n16, and the entity
policy vs nearest-server greedy on the inverted alt-pool layout and an
unseen E=3 pool. The streaming ledger enforces the QoS wins: the
streaming-fine-tuned entity dispatcher vs nearest-server on p99 sojourn
at mid load and deadline-miss rate at saturation (quick/full; smoke
enforces the training-free oracle on the same two gates). The
llm_offload ledger enforces the mixed CNN+LLM pool story: the entity
policy vs nearest-server greedy, and the long-context rung's realized
throughput vs its split table's Eq. 7/8 closed form (training-free —
gated in smoke too).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.launch.cache import enable_compile_cache


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _section(name):
    print(f"# --- {name} ---", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale RL iteration counts (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny iteration counts (CI smoke); artifacts are "
                         "still written")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    quick = not args.full
    smoke = args.smoke
    results = {}
    parity_checks = []   # (section, name, ratio, limit)

    def want(s):
        return args.only is None or args.only == s

    def guard(section, name, ratio, limit):
        parity_checks.append((section, name, float(ratio), float(limit)))

    print("name,us_per_call,derived")

    if want("kernels"):
        _section("kernels (interpret-mode timing + TPU roofline)")
        from benchmarks import bench_kernels
        out = bench_kernels.run()
        results["kernels"] = out
        for r in out["rows"]:
            _emit(r["name"], r["us_per_call"], r["derived"])

    if want("compression"):
        _section("fig4/5 compression (AE vs JALAD, xi ablation)")
        from benchmarks import bench_compression
        t0 = time.time()
        out = bench_compression.run(quick=quick, smoke=smoke)
        results["compression"] = out
        per = (time.time() - t0) * 1e6 / max(len(out["rows"]), 1)
        for r in out["rows"]:
            _emit(f"fig4_point{r['point']}", per,
                  f"ae_rate={r['ae_rate']:.0f};jalad_rate={r['jalad_rate']:.1f};"
                  f"acc={r['ae_acc']:.3f};base={r['base_acc']:.3f}")
        xi = bench_compression.run_xi_ablation(quick=quick, smoke=smoke)
        results["xi"] = xi
        for r in xi["rows"]:
            _emit(f"fig5_point{r['point']}_xi{r['xi']}", 0.0,
                  f"acc={r['acc']:.3f}")
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "compression", "schema": 1,
                    "smoke": smoke, "quick": quick,
                    "rows": out["rows"], "xi_rows": xi["rows"]}
        with open("artifacts/BENCH_compression.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_compression.json", flush=True)

    if want("overhead"):
        _section("fig7 overhead tables + long-task throughput rungs")
        from benchmarks import bench_overhead
        out = bench_overhead.run()
        results["overhead"] = out
        for r in out["rows"]:
            if r["backbone"] in ("resnet18", "qwen3-1.7b"):
                _emit(f"fig7_{r['backbone']}_b{r['b']}", 0.0,
                      f"t_ms={r['t_local_ms']:.1f};e_mJ={r['e_local_mJ']:.1f};"
                      f"f_kbits={r['f_kbits']:.0f}")
        # long-task rungs: completion throughput vs the Eq. 7/8 closed
        # form once t_task exceeds the frame length (the pre-PR-7 restart
        # bug starved exactly these; the ledger keeps them honest)
        long_out = bench_overhead.run_long_tasks(smoke=smoke)
        results["overhead_long_tasks"] = long_out
        for r in long_out["rows"]:
            _emit(f"overhead_long_task_x{r['frames_per_task']:.1f}", 0.0,
                  f"t_task_ms={r['t_task_ms']:.1f};"
                  f"expected={r['expected_per_frame']:.4f};"
                  f"realized={r['realized_per_frame']:.4f};"
                  f"ratio={r['ratio']:.3f}")
        for p in long_out["parity"]:
            guard("overhead", p["name"], p["ratio"], p["limit"])
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "overhead", "schema": 1,
                    "smoke": smoke, "quick": quick,
                    "fig7_rows": out["rows"],
                    "long_task_rows": long_out["rows"],
                    "parity": long_out["parity"]}
        with open("artifacts/BENCH_overhead.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_overhead.json", flush=True)

    if want("convergence"):
        _section("fig8 convergence (MAHPPO vs local vs JALAD)")
        from benchmarks import bench_convergence
        t0 = time.time()
        out = bench_convergence.run(quick=quick)
        results["convergence"] = out
        iters = len(out["mahppo_curve"])
        us = (time.time() - t0) * 1e6 / max(iters, 1)
        _emit("fig8_mahppo_final_reward", us,
              f"{np.mean(out['mahppo_curve'][-5:]):.4f}")
        # JALAD runs at T0=3s (paper relaxation); per-frame rewards are
        # throughput-normalized by the reward definition, so raw values
        # compare directly (more negative = worse).
        _emit("fig8_jalad_final_reward", us,
              f"{np.mean(out['jalad_curve'][-5:]):.4f}")
        ev = out["eval"]
        _emit("fig8_eval_t_ms", us,
              f"mahppo={1e3*ev['mahppo']['t_task']:.1f};"
              f"local={1e3*ev['local']['t_task']:.1f}")
        _emit("fig8_eval_e_mJ", us,
              f"mahppo={1e3*ev['mahppo']['e_task']:.1f};"
              f"local={1e3*ev['local']['e_task']:.1f}")
        for name, r in out.get("refs", {}).items():
            _emit(f"fig8_ref_{name}", 0.0,
                  f"t_ms={1e3*r['t_task']:.1f};e_mJ={1e3*r['e_task']:.1f};"
                  f"overhead={r['overhead']:.4f}")

    if want("hparams"):
        _section("fig9 hyperparameter sweeps (lr / reuse / memory)")
        from benchmarks import bench_convergence
        t0 = time.time()
        out = bench_convergence.run_hparams(quick=quick)
        results["hparams"] = out
        us = (time.time() - t0) * 1e6 / max(len(out), 1)
        for k, v in out.items():
            _emit(f"fig9_{k}", us, f"final_reward={v:.4f}")

    if want("ue_scaling"):
        _section("giant-fleet scaling (per-UE iteration cost N=16..1024 "
                 "+ fused pair-scorer kernel)")
        from benchmarks import bench_ue_scaling
        out = bench_ue_scaling.run(quick=quick, smoke=smoke)
        results["ue_scaling"] = out
        for r in out["rows"]:
            _emit(f"ue_scaling_n{r['n_ue']}", r["iter_us"],
                  f"per_ue_us={r['per_ue_us']:.1f};frames={r['frames']}")
        for r in out["kernel_rows"]:
            _emit(f"pair_scorer_n{r['n']}", r["fused_us"],
                  f"ref_us={r['ref_us']:.1f};ratio={r['ratio']:.2f};"
                  f"max_diff={r['max_diff']:.2e};"
                  f"pallas_max_diff={r['pallas_max_diff']:.2e}")
        _emit("ue_scaling_per_ue_sublinear", 0.0,
              f"ratio={out['per_ue_sublinear']:.3f};"
              f"limit={bench_ue_scaling.SUBLINEAR_LIMIT}")
        for p in out["parity"]:
            guard("ue_scaling", p["name"], p["ratio"], p["limit"])
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "ue_scaling", "schema": 1,
                    "smoke": smoke, "quick": quick,
                    "rows": out["rows"],
                    "kernel_rows": out["kernel_rows"],
                    "per_ue_sublinear": out["per_ue_sublinear"],
                    "parity": out["parity"]}
        with open("artifacts/BENCH_ue_scaling.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_ue_scaling.json", flush=True)

    if want("beta"):
        _section("fig12 beta trade-off")
        from benchmarks import bench_beta
        t0 = time.time()
        out = bench_beta.run(quick=quick)
        results["beta"] = out
        us = (time.time() - t0) * 1e6 / max(len(out["rows"]), 1)
        for r in out["rows"]:
            _emit(f"fig12_beta{r['beta']}", us,
                  f"t_ms={r['t_ms']:.1f};e_mJ={r['e_mJ']:.1f}")

    if want("hetero"):
        _section("heterogeneous fleet (mixed backbones + device tiers)")
        from benchmarks import bench_hetero_fleet
        out = bench_hetero_fleet.run(quick=quick)
        results["hetero"] = out
        for r in out["rows"]:
            _emit(f"hetero_{r['policy']}", 0.0,
                  f"t_ms={1e3*r['t_task']:.1f};e_mJ={1e3*r['e_task']:.1f};"
                  f"overhead={r['overhead']:.4f};reward={r['reward']:.4f}")
        _emit("hetero_iter_us", out["iter_us_mixed"],
              f"homogeneous_us={out['iter_us_homogeneous']:.0f}")
        guard("hetero", "mixed_vs_homogeneous_iteration",
              out["iter_us_mixed"] / max(out["iter_us_homogeneous"], 1e-9),
              1.5)

    if want("churn"):
        _section("dynamic fleet (UE churn: join/leave mid-episode)")
        from benchmarks import bench_churn
        out = bench_churn.run(quick=quick)
        results["churn"] = out
        for r in out["rows"]:
            _emit(f"churn_{int(100*r['churn'])}pct", 0.0,
                  f"mahppo={r['mahppo_reward']:.4f};"
                  f"local={r['local_reward']:.4f};"
                  f"t_ms={1e3*r['t_task']:.1f};"
                  f"fleet={r['n_active_mean']:.2f};"
                  f"beats_local={r['beats_local']}")
        _emit("churn_iter_us", out["iter_us_churn"],
              f"static_us={out['iter_us_static']:.0f};"
              f"ratio={out['iter_ratio']:.2f}")
        guard("churn", "churn_vs_static_iteration", out["iter_ratio"], 1.5)

    if want("multi_server"):
        _section("multi-server edge pool (routed action space)")
        from benchmarks import bench_multi_server
        out = bench_multi_server.run(quick=quick, smoke=smoke)
        results["multi_server"] = out
        for r in out["rows"]:
            _emit(f"multi_server_{r['policy']}", 0.0,
                  f"overhead={r['overhead']:.4f};"
                  f"t_ms={1e3*r['t_task']:.1f};"
                  f"e_mJ={1e3*r['e_task']:.1f}"
                  + (f";route={''.join(map(str, r['route']))}"
                     if "route" in r else ""))
        _emit("multi_server_iter_us", out["iter_us_multi"],
              f"single_us={out['iter_us_single']:.0f};"
              f"ratio={out['iter_ratio']:.2f};"
              f"beats_nearest={out['beats_nearest']}")
        for p in out["parity"]:
            guard("multi_server", p["name"], p["ratio"], p["limit"])
        # routing under churn: sparse membership vs flash crowd
        churn_out = bench_multi_server.run_churn_routing(quick=quick,
                                                         smoke=smoke)
        results["multi_server_churn_routing"] = churn_out
        _emit("multi_server_churn_routing", 0.0,
              f"sparse_share={churn_out['sparse']['max_share']:.2f};"
              f"flash_share={churn_out['flash']['max_share']:.2f};"
              f"flash_counts="
              f"{''.join(map(str, churn_out['flash']['counts']))};"
              f"rebalances={churn_out['rebalances']}")
        for p in churn_out["parity"]:
            guard("multi_server", p["name"], p["ratio"], p["limit"])
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "multi_server", "schema": 2,
                    "smoke": smoke, "quick": quick,
                    "rows": out["rows"],
                    "beats_nearest": out["beats_nearest"],
                    "iter_us_single": out["iter_us_single"],
                    "iter_us_multi": out["iter_us_multi"],
                    "iter_ratio": out["iter_ratio"],
                    "churn_routing": churn_out,
                    "parity": out["parity"] + churn_out["parity"]}
        with open("artifacts/BENCH_multi_server.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_multi_server.json", flush=True)

    if want("llm_offload"):
        _section("llm decode offloading (mixed CNN+LLM pool, context "
                 "rungs)")
        from benchmarks import bench_llm_offload
        out = bench_llm_offload.run(quick=quick, smoke=smoke)
        results["llm_offload"] = out
        for r in out["rows"]:
            _emit(f"llm_offload_{r['policy']}", 0.0,
                  f"overhead={r['overhead']:.4f};"
                  f"t_s={r['t_task']:.3f};"
                  f"e_mJ={1e3*r['e_task']:.1f}"
                  + (f";route={''.join(map(str, r['route']))}"
                     if "route" in r else ""))
        for m in out["modes"]["rows"]:
            _emit(f"llm_offload_mode_{m['ue']}", 0.0,
                  f"split={m['split']};local={m['local']};"
                  f"server={m['route']}")
        _emit("llm_offload_ctx_shift", 0.0,
              f"ctx_shift={out['ctx_shift']};"
              f"beats_nearest={out['beats_nearest']}")
        for r in out["flops_rows"]:
            _emit(f"llm_offload_flops_ctx{r['ctx']}", 0.0,
                  f"table={r['table_flops']:.3e};"
                  f"convention={r['convention_flops']:.3e};"
                  f"ratio={r['ratio']:.2f}")
        for p in out["parity"]:
            guard("llm_offload", p["name"], p["ratio"], p["limit"])
        cf = bench_llm_offload.run_closed_form(smoke=smoke)
        results["llm_offload_closed_form"] = cf
        for r in cf["rows"]:
            _emit(f"llm_offload_closed_form_{r['rung']}", 0.0,
                  f"t_task_s={r['t_task_s']:.1f};"
                  f"expected={r['expected_per_frame']:.4f};"
                  f"realized={r['realized_per_frame']:.4f};"
                  f"ratio={r['ratio']:.3f}")
        for p in cf["parity"]:
            guard("llm_offload", p["name"], p["ratio"], p["limit"])
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "llm_offload", "schema": 1,
                    "smoke": smoke, "quick": quick,
                    "rows": out["rows"], "modes": out["modes"],
                    "ctx_shift": out["ctx_shift"],
                    "beats_nearest": out["beats_nearest"],
                    "flops_rows": out["flops_rows"],
                    "closed_form_rows": cf["rows"],
                    "train_s": out["train_s"],
                    "parity": out["parity"] + cf["parity"]}
        with open("artifacts/BENCH_llm_offload.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_llm_offload.json", flush=True)

    if want("generalization"):
        _section("fleet-generalist shared policy (zero-shot N / pool "
                 "transfer)")
        from benchmarks import bench_generalization
        out = bench_generalization.run(quick=quick, smoke=smoke)
        results["generalization"] = out
        for r in out["rows"]:
            _emit(f"generalization_{r['scenario']}", 0.0,
                  f"n_ue={r['n_ue']};"
                  f"shared={r['shared_overhead']:.4f};"
                  f"greedy={r['greedy_overhead']:.4f};"
                  f"beats_greedy={r['beats_greedy']}"
                  + (f";per_ue={r['per_ue_overhead']:.4f}"
                     if "per_ue_overhead" in r else ""))
        for r in out["entity_rows"]:
            _emit(f"generalization_{r['scenario']}", 0.0,
                  f"n_servers={r['n_servers']};"
                  f"entity={r['entity_overhead']:.4f};"
                  f"nearest={r['nearest_overhead']:.4f};"
                  f"greedy={r['greedy_overhead']:.4f};"
                  f"beats_nearest={r['beats_nearest']}")
        p = out["params"]
        _emit("generalization_params", 0.0,
              f"shared={p['shared']};entity={p['entity']};"
              + ";".join(f"per_ue_n{n}={c}"
                         for n, c in sorted(p["per_ue"].items()))
              + f";sublinear={out['param_sublinear']}")
        _emit("generalization_iter_us", out["iter_us_shared"],
              f"per_ue_us={out['iter_us_per_ue']:.0f};"
              f"entity_us={out['iter_us_entity']:.0f};"
              f"ratio={out['iter_ratio']:.2f};"
              f"entity_ratio={out['entity_iter_ratio']:.2f};"
              f"zero_shot_beats_greedy={out['zero_shot_beats_greedy']}")
        for pc in out["parity"]:
            guard("generalization", pc["name"], pc["ratio"], pc["limit"])
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "generalization", "schema": 2,
                    "smoke": smoke, "quick": quick,
                    "rows": out["rows"], "params": out["params"],
                    "param_sublinear": out["param_sublinear"],
                    "zero_shot_beats_greedy":
                        out["zero_shot_beats_greedy"],
                    "iter_us_per_ue": out["iter_us_per_ue"],
                    "iter_us_shared": out["iter_us_shared"],
                    "iter_ratio": out["iter_ratio"],
                    "parity": out["parity"]}
        with open("artifacts/BENCH_generalization.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_generalization.json", flush=True)
        # standalone entity-policy artifact: the pool-transfer story
        # (alt-pool + unseen-E wins, scorer parity) in one place
        entity_artifact = {
            "bench": "entity", "schema": 1, "smoke": smoke, "quick": quick,
            "rows": out["entity_rows"],
            "entity_params": p["entity"],
            "entity_train_s": out["entity_train_s"],
            "iter_us_shared": out["iter_us_shared"],
            "iter_us_entity": out["iter_us_entity"],
            "iter_us_entity_randomized": out["iter_us_entity_randomized"],
            "entity_iter_ratio": out["entity_iter_ratio"],
            "parity": [g for g in out["parity"]
                       if g["name"].startswith("entity")]}
        with open("artifacts/BENCH_entity.json", "w") as f:
            json.dump(entity_artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_entity.json", flush=True)

    if want("streaming"):
        _section("streaming serve (continuous-time arrivals, deadline QoS, "
                 "policy-as-dispatcher)")
        from benchmarks import bench_streaming
        out = bench_streaming.run(quick=quick, smoke=smoke)
        results["streaming"] = out
        for r in out["rows"]:
            _emit(f"streaming_rate{r['rate']:g}_{r['dispatcher']}", 0.0,
                  f"miss={r['miss_rate']:.3f};p99={r['sojourn_p99']:.3f};"
                  f"thr={r['throughput']:.1f};spread={r['spread']:.2f};"
                  f"seeds={r['eval_seeds']}")
        lat = out["entity_dispatch_us"]
        if lat:
            _emit("streaming_entity_dispatch_us", lat["p50"],
                  f"p95={lat['p95']:.0f};p99={lat['p99']:.0f}")
        fwd = out["policy_forward_us"]
        _emit("streaming_policy_forward_us", fwd["best_us"],
              f"mean={fwd['mean_us']:.1f};p99={fwd['tail']['p99']:.1f}")
        _emit("streaming_train_s", out["train_s"] * 1e6,
              f"tune_s={out['tune_s']:.1f};"
              f"tune_final_miss={out['tune_history'][-1]['miss_rate']:.3f}")
        for p in out["parity"]:
            guard("streaming", p["name"], p["ratio"], p["limit"])
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "streaming", "schema": 1,
                    "smoke": smoke, "quick": quick,
                    "rows": out["rows"],
                    "mid_rate": out["mid_rate"],
                    "sat_rate": out["sat_rate"],
                    "entity_dispatch_us": out["entity_dispatch_us"],
                    "policy_forward_us": out["policy_forward_us"],
                    "train_s": out["train_s"], "tune_s": out["tune_s"],
                    "tune_history": out["tune_history"],
                    "parity": out["parity"]}
        with open("artifacts/BENCH_streaming.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_streaming.json", flush=True)

    if want("policy_latency"):
        _section("policy latency (train big, serve small: distilled + "
                 "int8 trunk)")
        from benchmarks import bench_policy_latency
        out = bench_policy_latency.run(quick=quick, smoke=smoke)
        results["policy_latency"] = out
        for r in out["rows"]:
            _emit(f"policy_latency_{r['candidate']}_b{r['batch']}",
                  r["best_us"],
                  f"us_per_decision={r['us_per_decision']:.3f};"
                  f"p50={r['p50_us']:.1f};p99={r['p99_us']:.1f}")
        p = out["params"]
        _emit("policy_latency_params", 0.0,
              f"teacher={p['teacher']};student={p['student']};"
              f"ratio={p['ratio']:.3f};"
              f"bytes_f32={p['student_bytes_f32']};"
              f"bytes_int8={p['student_bytes_int8']}")
        fid = out["fidelity"]
        _emit("policy_latency_fidelity", 0.0,
              f"ratio_f32={fid['ratio_f32']:.3f};"
              f"ratio_int8={fid['ratio_int8']:.3f};"
              f"mode_agree={fid['agreement']['all']:.3f}")
        ker = out["kernel"]
        _emit("policy_latency_int8_kernel", 0.0,
              f"max_diff_xla={ker['kernel_max_diff']['xla']:.2e};"
              f"max_diff_pallas={ker['kernel_max_diff']['pallas']:.2e};"
              f"int8_vs_f32_agree={ker['int8_vs_f32_mode_agree']:.4f}")
        _emit("policy_latency_stream_mid", 0.0,
              f"trunk_p99={out['stream']['trunk']['sojourn_p99']:.3f};"
              f"nearest_p99={out['stream']['nearest']['sojourn_p99']:.3f};"
              f"ratio={out['stream']['p99_ratio']:.3f}")
        for pc in out["parity"]:
            guard("policy_latency", pc["name"], pc["ratio"], pc["limit"])
        os.makedirs("artifacts", exist_ok=True)
        artifact = {"bench": "policy_latency", "schema": 1,
                    "smoke": smoke, "quick": quick,
                    "rows": out["rows"], "params": out["params"],
                    "fidelity": out["fidelity"], "kernel": out["kernel"],
                    "stream": out["stream"],
                    "batch1_speedup": out["batch1_speedup"],
                    "batches": out["batches"],
                    "train_s": out["train_s"], "tune_s": out["tune_s"],
                    "distill_s": out["distill_s"],
                    "distill_history": out["distill_history"],
                    "parity": out["parity"]}
        with open("artifacts/BENCH_policy_latency.json", "w") as f:
            json.dump(artifact, f, indent=1, default=float)
        print("# wrote artifacts/BENCH_policy_latency.json", flush=True)

    if want("archs"):
        _section("fig13 other backbones (+ assigned archs)")
        from benchmarks import bench_archs
        t0 = time.time()
        out = bench_archs.run(quick=quick)
        results["archs"] = out
        us = (time.time() - t0) * 1e6 / max(len(out["rows"]), 1)
        for k, v in out["rows"].items():
            _emit(f"fig13_{k}", us,
                  f"t_ms={v['t_ms']:.1f};e_mJ={v['e_mJ']:.1f};"
                  f"local_t={v['local_t_ms']:.1f};local_e={v['local_e_mJ']:.1f}")

    if want("roofline"):
        _section("roofline (from dry-run artifacts)")
        from benchmarks import roofline
        rows = roofline.full_table(roofline.default_art_dir())
        if rows:
            for r in rows:
                if r["mesh"] == "16x16":
                    _emit(f"roofline_{r['arch']}_{r['shape']}", 0.0,
                          f"compute_s={r['t_compute_s']:.2e};"
                          f"memory_s={r['t_memory_s']:.2e};"
                          f"coll_s={r['t_collective_s']:.2e};"
                          f"dom={r['dominant']};useful={r['useful_ratio']:.2f}")
            with open("artifacts/roofline.json", "w") as f:
                json.dump(rows, f, indent=1)
        else:
            _emit("roofline_missing", 0.0,
                  "run `python -m repro.launch.dryrun --all` first")

    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/bench_results.json", "w") as f:
        json.dump(results, f, indent=1, default=float)
    print("# wrote artifacts/bench_results.json", flush=True)

    # fail LOUDLY on any jitted-iteration parity regression: a hot-path
    # slowdown must stop the build, not scroll past as a ratio.
    failures = [(s, n, r, lim) for s, n, r, lim in parity_checks if r > lim]
    for s, n, r, lim in parity_checks:
        status = "FAIL" if r > lim else "ok"
        print(f"# parity[{s}] {n}: ratio {r:.2f} (limit {lim:.2f}) "
              f"{status}", flush=True)
    if failures:
        print(f"# PARITY REGRESSION: {len(failures)}/{len(parity_checks)} "
              "guard(s) exceeded their limit", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
