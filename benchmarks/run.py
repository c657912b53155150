"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time per
generated token or per kernel call where applicable; derived = the
headline metric of that artifact) and writes the full records to
results/benchmarks.json.

Usage: PYTHONPATH=src:. python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def main() -> None:
    fast = "--fast" in sys.argv
    t_all = time.time()
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    n_eval = 3 if fast else 6
    evalset = PC.eval_set(task, n_eval)
    results = {}

    def record(name, payload, us_per_call, derived):
        results[name] = payload
        print(f"{name},{us_per_call:.1f},{derived}", flush=True)

    # ---- offline profiling (§5) -----------------------------------------
    t0 = time.time()
    dev0 = PC.make_device(slm_cfg, slm_p)
    eng0 = PC.make_engine(llm_cfg, llm_p)
    profile, prof_run = PC.profile_pair(dev0, eng0, evalset, task)
    n_tok = sum(len(m.tokens) for m in prof_run.metrics)
    record("profiling_sec5", dict(c_th=profile.c_th, alpha=profile.alpha,
                                  gamma=profile.gamma),
           (time.time() - t0) / max(n_tok, 1) * 1e6,
           f"c_th={profile.c_th:.3f};alpha={profile.alpha:.3f}")

    # ---- Fig 4 ----------------------------------------------------------
    t0 = time.time()
    f4 = PC.fig4(task, slm_cfg, slm_p, llm_cfg, llm_p,
                 n_seq=4 if fast else 8)
    record("fig4_confidence", f4, (time.time() - t0) * 1e6 / 8,
           f"frac_conf>0.8={f4['frac_conf_above_0.8']:.3f}")

    # ---- Fig 5a: oracle importance vs random (the paper's protocol) -----
    budgets = (0.0, 0.2, 0.5, 1.0) if fast else (0.0, 0.1, 0.2, 0.3, 0.5,
                                                 0.8, 1.0)
    t0 = time.time()
    f5 = PC.fig5_oracle(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset,
                        budgets=budgets)
    q_imp = {r["budget"]: r["quality"] for r in f5 if r["mode"] == "oracle"}
    q_rnd = {r["budget"]: r["quality"] for r in f5 if r["mode"] == "random"}
    n_tok = n_eval * PC.GEN * len(budgets) * 2
    record("fig5_oracle", f5, (time.time() - t0) / n_tok * 1e6,
           f"q@0.2(imp)={q_imp.get(0.2, 0):.3f};q@0.2(rand)={q_rnd.get(0.2, 0):.3f}")

    # ---- Fig 14: runtime dual-metric budget sweep ------------------------
    t0 = time.time()
    f14 = PC.budget_sweep(task, slm_cfg, slm_p, llm_cfg, llm_p,
                          evalset, profile, budgets=budgets, mode="both")
    k02 = next((r for r in f14 if abs(r["budget"] - 0.2) < 1e-9), f14[0])
    record("fig14_tradeoff", f14, (time.time() - t0) / n_tok * 1e6,
           f"q@0.2={k02['quality']:.3f};cost@0.2={k02['cost']:.2f};"
           f"tbt@0.2={k02['tbt_ms']:.0f}ms")

    # ---- Table 4 / Fig 11 / Fig 12 --------------------------------------
    t0 = time.time()
    methods = PC.methods_comparison(task, slm_cfg, slm_p, llm_cfg, llm_p,
                                    evalset, profile)
    by = {r["method"]: r for r in methods}
    n_tok = n_eval * PC.GEN * len(methods)
    gain = by["synera"]["quality"] / max(by["edge-centric"]["quality"], 1e-9)
    cost_cut = 1 - by["synera"]["cost"] / max(by["cloud-centric"]["cost"],
                                              1e-9)
    record("table4_fig11_fig12_methods", methods,
           (time.time() - t0) / n_tok * 1e6,
           f"quality_gain_vs_edge={gain:.2f}x;cost_cut_vs_cloud={cost_cut:.2%}")

    # ---- Fig 13 ----------------------------------------------------------
    t0 = time.time()
    bw = PC.bandwidth_sweep(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset,
                            profile,
                            bandwidths=(0.1, 10.0) if fast
                            else (0.1, 1.0, 10.0, 100.0))
    lo_c = [r for r in bw if r["bandwidth_mbps"] == 0.1 and r["compression"]]
    lo_n = [r for r in bw if r["bandwidth_mbps"] == 0.1 and not r["compression"]]
    record("fig13_bandwidth", bw, (time.time() - t0) * 1e3,
           f"tbt@0.1Mbps comp={lo_c[0]['tbt_ms']:.0f}ms "
           f"nocomp={lo_n[0]['tbt_ms']:.0f}ms")

    # ---- Fig 15 ----------------------------------------------------------
    t0 = time.time()
    sc = PC.scalability()
    knees = {}
    for b in (0.3, 0.6, 0.9):
        rs = [r for r in sc if r["budget"] == b]
        base = rs[0]["mean_ms"]
        knee = next((r["rate"] for r in rs if r["mean_ms"] > 5 * base),
                    rs[-1]["rate"])
        knees[b] = knee
    record("fig15_scalability", sc, (time.time() - t0) * 1e6 / len(sc),
           f"saturation_rates={knees}")

    # ---- Fig 17 ----------------------------------------------------------
    t0 = time.time()
    ths = (0.0, 0.8, 1.0) if fast else (0.0, 0.3, 0.6, 0.8, 1.0)
    ee = PC.early_exit_sweep(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset,
                             profile, thresholds=ths)
    q1 = next(r for r in ee if r["threshold"] == max(ths))
    q08 = next(r for r in ee if abs(r["threshold"] - 0.8) < 1e-9)
    record("fig17_early_exit", ee,
           (time.time() - t0) / (n_eval * PC.GEN * len(ee)) * 1e6,
           f"q@0.8={q08['quality']:.3f} vs q@1.0={q1['quality']:.3f};"
           f"layers_saved@0.8={q08['layers_saved']:.2%}")

    # ---- Fig 18 + §6.5 ----------------------------------------------------
    t0 = time.time()
    oh = PC.overhead_and_hits(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset,
                              profile)
    record("fig18_sec65_overhead_pihits", oh, (time.time() - t0) * 1e3,
           f"pi_hit@0.5={oh[1]['pi_hit_rate']:.2f};"
           f"sched_overhead@0.8={oh[2]['sched_overhead']:.2%}")

    # ---- Table 6 (§6.8): quantization complementarity --------------------
    t0 = time.time()
    tq = PC.quantization_table(task, slm_cfg, slm_p, llm_cfg, llm_p,
                               evalset, profile)
    gains = {r["quant"]: r["rel_gain"] for r in tq}
    record("table6_quantization", tq,
           (time.time() - t0) / (n_eval * PC.GEN * 6) * 1e6,
           f"rel_gain fp32={gains.get('fp32', 0):.2f} "
           f"int8={gains.get('int8', 0):.2f} int4={gains.get('int4', 0):.2f}")

    # ---- kernel microbench ------------------------------------------------
    from benchmarks.kernel_bench import kernel_micro
    for row in kernel_micro():
        record(f"kernel_{row['name']}", row, row["us_per_call"],
               f"max_err={row['max_err']:.1e}")

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"# total {time.time()-t_all:.1f}s -> results/benchmarks.json",
          flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
