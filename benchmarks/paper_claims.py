"""Benchmarks reproducing each of Synera's tables/figures on the trained
tiny SLM/LLM pair + synthetic tasks (exact ground-truth scoring).

One function per paper artifact:
  fig4   — SLM->LLM hit rate vs confidence; confidence CDF
  fig5   — quality vs offloading budget (importance vs random); imp CDF
  table4 — generation quality: edge / cloud / EdgeFM / Hybrid / Synera
  fig11  — latency (TBT) + ablations (w/o PI, conf-only, imp-only)
  fig12  — estimated cloud serving cost per method
  fig13  — bandwidth sweep with/without compression
  fig14  — quality/cost/latency vs budget trade-off
  fig15  — cloud scalability: verification latency vs request rate
  fig17  — layer-wise early-exit threshold sweep
  fig18  — verification-aware scheduler overhead vs budget
  sec65  — rejection-position prediction hit rate
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.early_exit import EarlyExitConfig
from repro.core.offload import OffloadPolicy
from repro.core.profiling import fit_profile
from repro.models import model as M
from repro.serving.device import DeviceRuntime
from repro.serving.engine import CloudEngine
from repro.serving.link import (CloudLatencyModel, CostModel,
                                DeviceLatencyModel, LinkModel)
from repro.serving import synergy as SY

GAMMA = 4
S_MAX = 192
PLEN = 40
GEN = 40


# ---------------------------------------------------------------------------
# Shared evaluation machinery
# ---------------------------------------------------------------------------

def eval_set(task, n: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        seq, regimes = task.sample_sequence(PLEN + GEN, rng)
        out.append((list(map(int, seq[:PLEN])), regimes))
    return out


def score_outputs(task, evalset, outputs):
    scores = []
    for (prompt, regimes), toks in zip(evalset, outputs):
        full = np.array(prompt + [int(t) for t in toks])
        scores.append(task.score(full, regimes, PLEN))
    return {k: float(np.mean([s[k] for s in scores])) for k in scores[0]}


def make_device(slm_cfg, slm_p, policy=None, **kw):
    # wire_vocab: payload accounting at Llama-2 production vocab (§4.2)
    defaults = dict(s_max=S_MAX, gamma=GAMMA, seed=0, sampling="greedy",
                    wire_vocab=32_000)
    defaults.update(kw)
    return DeviceRuntime(slm_cfg, slm_p, policy=policy, **defaults)


def make_engine(llm_cfg, llm_p, slots: int = 2, attn_impl: str | None = None,
                verify_top_k: int = 8, cache_impl: str | None = None,
                block_size: int | None = None,
                pool_blocks: int | None = None,
                share_prefix: bool | None = None,
                swap: bool | None = None,
                host_swap_blocks: int | None = None,
                retain_prefix: bool | None = None,
                retain_blocks: int | None = None,
                host_dedupe: bool | None = None,
                paged_block_kv: int | None = None,
                kv_splits: int | None = None,
                s_max: int = S_MAX, device=None):
    cfg = llm_cfg if attn_impl is None else llm_cfg.replace(
        attn_impl=attn_impl)
    return CloudEngine(cfg, llm_p, max_slots=slots, s_max=s_max,
                       verify_top_k=verify_top_k, cache_impl=cache_impl,
                       block_size=block_size, pool_blocks=pool_blocks,
                       share_prefix=share_prefix, swap=swap,
                       host_swap_blocks=host_swap_blocks,
                       retain_prefix=retain_prefix,
                       retain_blocks=retain_blocks,
                       host_dedupe=host_dedupe,
                       paged_block_kv=paged_block_kv, kv_splits=kv_splits,
                       device=device)


def profile_pair(dev, eng, evalset, task, max_new: int = GEN):
    """Offline profiling (§5): offload-all calibration pass."""
    r = SY.run_synera(dev, eng, [p for p, _ in evalset], max_new,
                      profile_mode=True)
    recs = [c for m in r.metrics for c in m.chunk_records]
    return fit_profile(recs), r


# ---------------------------------------------------------------------------
# Fig 4: hit rate vs confidence + confidence CDF
# ---------------------------------------------------------------------------

def fig4(task, slm_cfg, slm_p, llm_cfg, llm_p, n_seq: int = 8):
    rng = np.random.default_rng(3)
    confs, top1, top5 = [], [], []
    for _ in range(n_seq):
        seq, _ = task.sample_sequence(PLEN + GEN, rng)
        tk = jnp.asarray([seq], jnp.int32)
        pos = M.default_positions(1, len(seq))
        ls, _, _, _ = M.forward(slm_cfg, slm_p, tk, pos)
        ll, _, _, _ = M.forward(llm_cfg, llm_p, tk, pos)
        ps = jax.nn.softmax(ls[0].astype(jnp.float32), -1)
        conf = np.asarray(ps.max(-1))
        s_top5 = np.asarray(jax.lax.top_k(ps, 5)[1])
        l_top1 = np.asarray(jnp.argmax(ll[0], -1))
        confs += conf[:-1].tolist()
        top1 += (np.asarray(jnp.argmax(ls[0], -1)) == l_top1)[:-1].tolist()
        top5 += [(l_top1[i] in s_top5[i]) for i in range(len(seq) - 1)]
    confs = np.array(confs); top1 = np.array(top1); top5 = np.array(top5)
    bins = np.linspace(0, 1, 6)
    rows = []
    for lo, hi in zip(bins[:-1], bins[1:]):
        m = (confs >= lo) & (confs < hi if hi < 1 else confs <= hi)
        if m.sum() < 3:
            rows.append((f"{lo:.1f}-{hi:.1f}", None, None, int(m.sum())))
            continue
        rows.append((f"{lo:.1f}-{hi:.1f}", float(top1[m].mean()),
                     float(top5[m].mean()), int(m.sum())))
    frac_above_08 = float((confs > 0.8).mean())
    return {"bins": rows, "frac_conf_above_0.8": frac_above_08,
            "paper_claim": "hit rate rises with confidence; only ~16% of "
                           "tokens exceed 0.8 (Fig 4b)"}


# ---------------------------------------------------------------------------
# Fig 5a: the paper's oracle measurement protocol — rank chunks by
# FULL-CONTEXT importance (column sums over the whole SLM generation,
# including attention from future tokens) and offload the top-n%.
# ---------------------------------------------------------------------------

def fig5_oracle(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset,
                budgets=(0.0, 0.1, 0.2, 0.3, 0.5, 0.8, 1.0),
                modes=("oracle", "random")):
    from repro.models import model as MM
    eng = make_engine(llm_cfg, llm_p)
    prompts = [p for p, _ in evalset]
    dev0 = make_device(slm_cfg, slm_p, policy=OffloadPolicy(mode="none"))
    base = SY.run_edge_centric(dev0, prompts, GEN)

    # full-context importance per chunk of each SLM-only generation
    chunk_scores = []
    for prompt, out in zip(prompts, base.outputs):
        seq = jnp.asarray([list(prompt) + [int(t) for t in out]], jnp.int32)
        _, _, imp, _ = MM.forward(
            slm_cfg.replace(attn_impl="naive"), slm_p, seq,
            MM.default_positions(1, seq.shape[1]), return_importance=True)
        gen_imp = np.asarray(imp[0])[len(prompt):]
        n_chunks = len(gen_imp) // GAMMA
        chunk_scores.append(np.array([
            gen_imp[i * GAMMA:(i + 1) * GAMMA].mean()
            for i in range(n_chunks)]))

    rng = np.random.default_rng(11)
    rows = []
    for mode in modes:
        for b in budgets:
            outs = []
            for i, prompt in enumerate(prompts):
                cs = chunk_scores[i]
                n_off = int(round(b * len(cs)))
                if mode == "oracle":
                    picked = frozenset(np.argsort(-cs)[:n_off].tolist())
                else:
                    picked = frozenset(
                        rng.choice(len(cs), size=n_off,
                                   replace=False).tolist())
                dev = make_device(slm_cfg, slm_p,
                                  policy=OffloadPolicy(mode="chunk_set",
                                                       chunk_set=picked))
                r = SY.run_synera(dev, eng, [prompt], GEN)
                outs.append(r.outputs[0])
            s = score_outputs(task, evalset, outs)
            rows.append(dict(mode=mode, budget=b, quality=s["quality"],
                             copy_acc=s["copy_acc"], nll=s["nll"]))
    return rows


# ---------------------------------------------------------------------------
# Fig 14 (and runtime budget knob): dual-metric system budget sweeps
# ---------------------------------------------------------------------------

def budget_sweep(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset, profile,
                 budgets=(0.0, 0.1, 0.2, 0.3, 0.5, 0.8, 1.0),
                 mode: str = "imp"):
    eng = make_engine(llm_cfg, llm_p)
    cost_model = CostModel()
    rows = []
    for b in budgets:
        if b <= 0:
            pol = OffloadPolicy(mode="none")
        elif b >= 1:
            pol = OffloadPolicy(mode="all")
        elif mode == "random":
            pol = OffloadPolicy(mode="random", random_rate=b)
        else:
            pol = OffloadPolicy(c_th=profile.c_th,
                                i_th=profile.i_th_for_budget(b), mode=mode)
        dev = make_device(slm_cfg, slm_p, policy=pol, alpha=profile.alpha)
        r = SY.run_synera(dev, eng, [p for p, _ in evalset], GEN,
                          cost_model=cost_model)
        s = score_outputs(task, evalset, r.outputs)
        rows.append(dict(budget=b, mode=mode, quality=s["quality"],
                         copy_acc=s["copy_acc"], nll=s["nll"],
                         tbt_ms=r.tbt_ms, cost=r.cost,
                         cloud_frac=r.cloud_fed_frac,
                         offload_rate=float(np.mean(
                             [m.offload_rate for m in r.metrics]))))
    return rows


# ---------------------------------------------------------------------------
# Table 4 + Fig 11 + Fig 12: methods comparison (+ ablations)
# ---------------------------------------------------------------------------

def methods_comparison(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset,
                       profile, budget: float = 0.2):
    eng = make_engine(llm_cfg, llm_p)
    cost_model = CostModel()
    prompts = [p for p, _ in evalset]
    pol = OffloadPolicy(c_th=profile.c_th,
                        i_th=profile.i_th_for_budget(budget), mode="both")

    def run(name, fn):
        r = fn()
        s = score_outputs(task, evalset, r.outputs)
        return dict(method=name, quality=s["quality"], copy_acc=s["copy_acc"],
                    nll=s["nll"], tbt_ms=r.tbt_ms, cost=r.cost,
                    cloud_frac=r.cloud_fed_frac)

    dev = lambda **kw: make_device(slm_cfg, slm_p, policy=pol,
                                   alpha=profile.alpha, **kw)
    rows = [
        run("edge-centric", lambda: SY.run_edge_centric(
            make_device(slm_cfg, slm_p, policy=OffloadPolicy(mode="none")),
            prompts, GEN, cost_model=cost_model)),
        run("cloud-centric", lambda: SY.run_cloud_centric(
            eng, prompts, GEN, cost_model=cost_model)),
        run("edgefm-llm", lambda: SY.run_edgefm(
            dev(), eng, prompts, GEN, cost_model=cost_model)),
        run("hybrid", lambda: SY.run_hybrid(
            dev(), eng, prompts, GEN, cost_model=cost_model)),
        run("synera", lambda: SY.run_synera(
            dev(), eng, prompts, GEN, cost_model=cost_model)),
        # ablations (Fig 11 / Fig 16)
        run("synera-conf-only", lambda: SY.run_synera(
            make_device(slm_cfg, slm_p,
                        policy=OffloadPolicy(c_th=profile.c_th, mode="conf"),
                        alpha=profile.alpha),
            eng, prompts, GEN, cost_model=cost_model)),
        run("synera-imp-only", lambda: SY.run_synera(
            make_device(slm_cfg, slm_p,
                        policy=OffloadPolicy(
                            i_th=profile.i_th_for_budget(budget), mode="imp"),
                        alpha=profile.alpha),
            eng, prompts, GEN, cost_model=cost_model)),
        run("synera-no-pi", lambda: SY.run_synera(
            dev(use_pi=False), eng, prompts, GEN, cost_model=cost_model)),
        run("synera-no-ee", lambda: SY.run_synera(
            dev(use_early_exit=False), eng, prompts, GEN,
            cost_model=cost_model)),
    ]
    return rows


# ---------------------------------------------------------------------------
# Fig 13: bandwidth sweep
# ---------------------------------------------------------------------------

def bandwidth_sweep(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset, profile,
                    bandwidths=(0.1, 1.0, 10.0, 100.0), budget=0.35):
    eng = make_engine(llm_cfg, llm_p)
    prompts = [p for p, _ in evalset]
    pol = OffloadPolicy(c_th=profile.c_th,
                        i_th=profile.i_th_for_budget(budget), mode="both")
    rows = []
    for bw in bandwidths:
        for comp in (True, False):
            dev = make_device(slm_cfg, slm_p, policy=pol,
                              alpha=profile.alpha,
                              link=LinkModel(bandwidth_mbps=bw),
                              use_compression=comp)
            r = SY.run_synera(dev, eng, prompts, GEN)
            rows.append(dict(bandwidth_mbps=bw, compression=comp,
                             tbt_ms=r.tbt_ms,
                             uplink_kb=float(np.mean(
                                 [m.uplink_bytes for m in r.metrics]) / 1e3)))
    return rows


# ---------------------------------------------------------------------------
# Fig 15: scheduler scalability (queueing simulation over the latency model)
# ---------------------------------------------------------------------------

def scalability(budgets=(0.3, 0.6, 0.9),
                rates=(2, 5, 10, 15, 20, 25, 30, 40, 50, 60),
                sim_s: float = 20.0, seed: int = 0):
    """Poisson verification-request arrivals into the verification-aware
    scheduler's batching discipline (continuous batching over the cloud
    latency model).  Higher budgets issue more tokens per request (more
    offloaded chunks -> more uncached backlog per request), pushing the
    saturation knee to LOWER request rates — "lower budgets are more
    resilient under high throughput" (paper §6.4; note the paper's listed
    threshold<->budget pairing contradicts its own sentence — we follow
    the sentence).  Constants model a 13B verifier on A6000 (~100-400 ms
    per verification iteration, paper §3.3)."""
    lat = CloudLatencyModel(ms_base=25.0, ms_per_token=2.5,
                            ms_scheduler=0.5)
    rows = []
    rng = np.random.default_rng(seed)
    for budget in budgets:
        tokens_per_req = int(GAMMA + 1 + 12 * budget)
        for lam in rates:
            n = int(lam * sim_s)
            arrivals = np.sort(rng.uniform(0, sim_s, n)) * 1e3  # ms
            t = 0.0
            done = np.zeros(n)
            i = 0
            while i < n:
                t = max(t, arrivals[i])
                # batch everything that has arrived (continuous batching)
                j = i
                while j < n and arrivals[j] <= t:
                    j += 1
                batch = max(j - i, 1)
                iter_ms = lat.iteration_ms(batch * tokens_per_req)
                t += iter_ms
                done[i:j] = t
                i = j
            waits = done - arrivals
            rows.append(dict(budget=budget, rate=lam,
                             mean_ms=float(waits.mean()),
                             p95_ms=float(np.quantile(waits, 0.95))))
    return rows


# ---------------------------------------------------------------------------
# Fig 17: early-exit threshold sweep
# ---------------------------------------------------------------------------

def early_exit_sweep(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset, profile,
                     thresholds=(0.0, 0.3, 0.6, 0.8, 1.0), budget=0.35):
    eng = make_engine(llm_cfg, llm_p)
    prompts = [p for p, _ in evalset]
    pol = OffloadPolicy(c_th=profile.c_th,
                        i_th=profile.i_th_for_budget(budget), mode="both")
    rows = []
    for th in thresholds:
        dev = make_device(slm_cfg, slm_p, policy=pol, alpha=profile.alpha,
                          ee=EarlyExitConfig(threshold=th))
        r = SY.run_synera(dev, eng, prompts, GEN)
        s = score_outputs(task, evalset, r.outputs)
        rows.append(dict(threshold=th, quality=s["quality"],
                         tbt_ms=r.tbt_ms,
                         layers_saved=float(np.mean(
                             [m.mean_layers_saved for m in r.metrics]))))
    return rows


# ---------------------------------------------------------------------------
# Table 6 (§6.8): Synera + complementary SLM quantization
# ---------------------------------------------------------------------------

def quantization_table(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset,
                       profile, budget: float = 0.35):
    from repro.optim.quantize import quantize_params, speedup_factor
    eng = make_engine(llm_cfg, llm_p)
    prompts = [p for p, _ in evalset]
    pol = OffloadPolicy(c_th=profile.c_th,
                        i_th=profile.i_th_for_budget(budget), mode="both")
    rows = []
    for label, bits in (("fp32", 0), ("int8", 8), ("int4", 4)):
        params = quantize_params(slm_p, bits) if bits else slm_p
        lat = DeviceLatencyModel(
            ms_per_token=DeviceLatencyModel().ms_per_token
            / speedup_factor(bits) if bits else
            DeviceLatencyModel().ms_per_token)
        dev_e = make_device(slm_cfg, params, latency=lat,
                            policy=OffloadPolicy(mode="none"))
        r_e = SY.run_edge_centric(dev_e, prompts, GEN)
        s_e = score_outputs(task, evalset, r_e.outputs)
        dev_s = make_device(slm_cfg, params, latency=lat, policy=pol,
                            alpha=profile.alpha)
        r_s = SY.run_synera(dev_s, eng, prompts, GEN)
        s_s = score_outputs(task, evalset, r_s.outputs)
        rows.append(dict(
            quant=label,
            edge_quality=s_e["quality"], synera_quality=s_s["quality"],
            rel_gain=s_s["quality"] / max(s_e["quality"], 1e-9),
            edge_tbt=r_e.tbt_ms, synera_tbt=r_s.tbt_ms))
    return rows


# ---------------------------------------------------------------------------
# Fig 18: scheduler overhead + §6.5 PI hit rate + Table 5 energy
# ---------------------------------------------------------------------------

def overhead_and_hits(task, slm_cfg, slm_p, llm_cfg, llm_p, evalset, profile,
                      budgets=(0.2, 0.5, 0.8)):
    eng = make_engine(llm_cfg, llm_p)
    prompts = [p for p, _ in evalset]
    lat = CloudLatencyModel()
    rows = []
    for b in budgets:
        pol = OffloadPolicy(c_th=profile.c_th,
                            i_th=profile.i_th_for_budget(b), mode="both")
        dev = make_device(slm_cfg, slm_p, policy=pol, alpha=profile.alpha)
        r = SY.run_synera(dev, eng, prompts, GEN)
        pi_att = sum(m.pi_attempts for m in r.metrics)
        pi_hit = sum(m.pi_position_hits for m in r.metrics)
        pi_adopt = sum(m.pi_adopted for m in r.metrics)
        # scheduler overhead: fixed scheduling cost vs per-iteration compute
        fed = sum(m.n_cloud_fed_tokens for m in r.metrics)
        iters = max(1, fed // 32 + 1)
        sched_ms = iters * lat.ms_scheduler
        compute_ms = fed * lat.ms_per_token + iters * lat.ms_base
        energy = float(np.mean([m.timeline.energy_j /
                                max(len(m.tokens), 1) for m in r.metrics]))
        rows.append(dict(budget=b,
                         pi_hit_rate=pi_hit / max(pi_att, 1),
                         pi_adopt_rate=pi_adopt / max(pi_att, 1),
                         sched_overhead=sched_ms / max(compute_ms + sched_ms,
                                                       1e-9),
                         energy_j_per_token=energy))
    return rows
