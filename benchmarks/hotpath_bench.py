"""Cloud hot-path benchmark: fused on-device verification vs the PR-1
full-logits round trip (ROADMAP: make the hot path measurably faster).

At paper-scale vocabs the pre-change engine moved the full
(slots, chunk, V) float32 logits to the host EVERY verify iteration
(8 x 32 x 32768 x 4B = 32 MiB/iter at the default shape here; ~128 MiB
at Llama-3 128k vocab) and verified drafts in per-request host numpy.
The fused engine keeps the vocab axis device-resident: per row only an
argmax id, the gathered p(target) and a top-k support cross the
boundary — vocab-independent, ~72 B/row at K=8.

Both engines run the SAME synthetic verification workload (8 slots,
gamma=4 drafts, Sarathi chunk 32) through the real
VerificationAwareScheduler; greedy results are asserted byte-identical.
Wall time per verify iteration includes the host-side verifier work
(numpy argmax/stack for legacy, sparse-row decisions for fused), i.e.
the full scheduler iteration as served.

Usage:
  PYTHONPATH=src:. python -m benchmarks.hotpath_bench [--fast] \
      [--vocab 32768] [--out benchmarks/BENCH_hotpath.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def build_engine(vocab: int, slots: int, verify_top_k: int = 8):
    import jax
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    from repro.serving.engine import CloudEngine

    cfg = ModelConfig(
        name="hotpath-llm", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=4, d_ff=256, vocab=vocab,
        rope_theta=10_000.0, remat=False, dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return CloudEngine(cfg, params, max_slots=slots, s_max=256,
                       verify_top_k=verify_top_k)


def _make_workload(slots: int, rounds: int, gamma: int, vocab: int,
                   seed: int):
    """Per (round, slot): (uncached, draft, q_sparse) arrays, fixed up
    front so every mode serves the identical request stream."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, size=16).astype(np.int64)
               for _ in range(slots)]
    work = []
    for _ in range(rounds):
        per_slot = []
        for _ in range(slots):
            unc = rng.integers(1, vocab,
                               size=int(rng.integers(0, 3))).astype(np.int64)
            draft = rng.integers(1, vocab, size=gamma).astype(np.int64)
            q_sparse = []
            for _ in range(gamma):
                idx = rng.choice(vocab, size=8, replace=False) \
                    .astype(np.int32)
                val = rng.random(8)
                q_sparse.append((idx, (val / val.sum()).astype(np.float16)))
            per_slot.append((unc, draft, q_sparse))
        work.append(per_slot)
    return prompts, work


def run_mode(vocab: int, slots: int, rounds: int, *, fused: bool,
             sampling: str, gamma: int = 4, chunk: int = 32,
             seed: int = 11) -> dict:
    from repro.serving.scheduler import (PrefillRequest, VerifyRequest,
                                         VerificationAwareScheduler)

    engine = build_engine(vocab, slots)
    sched = VerificationAwareScheduler(engine, chunk=chunk, fused=fused,
                                       rng=np.random.default_rng(seed))
    prompts, work = _make_workload(slots, rounds + 1, gamma, vocab, seed)

    slot_of = {}
    for i, p in enumerate(prompts):
        sched.submit_prefill(PrefillRequest(i + 1, p))
    done = 0
    while done < slots:
        for ev in sched.run_iteration():
            slot_of[ev.req_id - 1] = ev.slot
            done += 1

    rid = slots
    results = []

    def run_round(per_slot):
        nonlocal rid
        want = set()
        for i, (unc, draft, q_sparse) in enumerate(per_slot):
            rid += 1
            want.add(rid)
            sched.submit_verify(VerifyRequest(
                rid, slot_of[i], uncached=unc, draft=draft,
                q_sparse=q_sparse, sampling=sampling))
        out = []
        while want:
            for ev in sched.run_iteration():
                want.discard(ev.req_id)
                out.append((ev.req_id, ev.result))
        return out

    run_round(work[0])                      # warmup: jit + verifier paths
    iters0 = sched.verify_iterations
    bytes0 = engine.bytes_to_host
    sim0 = sched.sim_ms
    t0 = time.perf_counter()
    for per_slot in work[1:]:
        results.extend(run_round(per_slot))
    wall_s = time.perf_counter() - t0
    n_iters = sched.verify_iterations - iters0
    n_bytes = engine.bytes_to_host - bytes0
    sim_ms = sched.sim_ms - sim0

    return dict(
        engine="fused" if fused else "legacy",
        sampling=sampling,
        verify_iterations=n_iters,
        # measured host wall time per scheduler iteration (engine step +
        # host verifier).  NOTE: CPU jax aliases device/host buffers, so
        # the legacy path's 32 MiB/iter "transfer" is free here; on real
        # accelerators it crosses the interconnect, which the modeled
        # number below charges at CloudLatencyModel.host_link_gbps.
        mean_iter_ms=wall_s / max(n_iters, 1) * 1e3,
        # modeled serving time per iteration (the repo's time axis for
        # every TBT/makespan number): compute + host-link transfer
        mean_iter_ms_modeled=sim_ms / max(n_iters, 1),
        host_bytes_per_verify_iter=n_bytes / max(n_iters, 1),
        wall_s=wall_s,
        mean_verify_occupancy=sched.mean_verify_occupancy,
        compile_stats=engine.compile_stats,
        results=[(r, res.n_accepted, res.tokens) for r, res in results],
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--vocab", type=int, default=32_768)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--out", default="benchmarks/BENCH_hotpath.json")
    args = ap.parse_args()
    rounds = 2 if args.fast else args.rounds

    rows = []
    identical = {}
    for sampling in ("greedy", "sample"):
        per_mode = {}
        for fused in (False, True):
            r = run_mode(args.vocab, args.slots, rounds, fused=fused,
                         sampling=sampling)
            per_mode[r["engine"]] = r
            print(f"{sampling:6s} {r['engine']:6s} "
                  f"iter={r['verify_iterations']} "
                  f"ms/iter={r['mean_iter_ms']:.1f} "
                  f"B/iter={r['host_bytes_per_verify_iter']:.0f}",
                  flush=True)
        if sampling == "greedy":
            identical["greedy_identical"] = (
                per_mode["fused"]["results"] == per_mode["legacy"]["results"])
            assert identical["greedy_identical"], \
                "fused greedy verification diverged from the host-numpy path"
        for r in per_mode.values():
            r.pop("results")
            rows.append(r)

    by = {(r["sampling"], r["engine"]): r for r in rows}
    reduction = dict(
        bytes=(by[("greedy", "legacy")]["host_bytes_per_verify_iter"]
               / by[("greedy", "fused")]["host_bytes_per_verify_iter"]),
        iter_time=(by[("greedy", "legacy")]["mean_iter_ms"]
                   / by[("greedy", "fused")]["mean_iter_ms"]),
        iter_time_modeled=(by[("greedy", "legacy")]["mean_iter_ms_modeled"]
                           / by[("greedy", "fused")]["mean_iter_ms_modeled"]),
    )
    res = dict(vocab=args.vocab, slots=args.slots, chunk=32, gamma=4,
               rounds=rounds, verify_top_k=8, rows=rows,
               reduction=reduction, **identical)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"bytes reduction {reduction['bytes']:.1f}x, "
          f"iter-time {reduction['iter_time']:.2f}x wall / "
          f"{reduction['iter_time_modeled']:.2f}x modeled; wrote {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
