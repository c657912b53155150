"""Scale benchmark: N concurrent device streams sharing one cloud
verifier under the SyneraServer event loop (ROADMAP: heavy traffic /
batching / async).

Three sweeps:

1. **Batching sweep** (``rows``): for each stream count the same request
   set is served twice on a fresh slot state: sequentially
   (``concurrency=1``, the old blocking semantics) and concurrently
   (``concurrency=N``).  Greedy token streams are identical by
   construction (asserted); what changes is packing:

   * verify-iteration batch occupancy (slots fed per iteration)
   * packed tokens per iteration
   * total scheduler iterations and cloud makespan (shared sim clock)
   * per-stream mean/p95 TBT (includes real cross-stream queueing)
   * estimated cloud cost (paper §6.1)

2. **Cache sweep** (``cache_rows``): dense vs paged KV cache at
   oversubscribed concurrency (more sessions than engine slots, the
   waiting-queue path).  Token streams are asserted identical; what
   changes is memory: the dense engine reserves ``slots x s_max``
   regardless of live lengths, the paged engine's footprint is its
   peak block usage — reported as *cache bytes per served token*.

3. **Shared-prefix sweep** (``shared_prefix_sweep``): N concurrent
   streams whose prompts share a long common system prefix, served on a
   paged engine with and without ``share_prefix``.  Outputs are asserted
   byte-identical (and identical to dense); what changes is peak pool
   usage — the common prefix's full blocks are allocated once and
   ref-counted into every stream's block table instead of once per
   stream.

4. **Preemption-pressure sweep** (``preempt_sweep``): recompute vs swap
   vs slo-aware eviction on a pool sized to force preemption, short vs
   long prompt prefixes at oversubscribed concurrency.  Outputs are
   asserted byte-identical across dispositions; the headline column is
   ``preempted_refed_tokens`` — recompute refeeds the victim's whole
   prefix, the host swap tier restores it bit-identical and refeeds
   nothing.

5. **Cross-session reuse sweep** (``cross_session_sweep``): N
   sequential non-overlapping waves of sessions sharing a system
   prompt, served with retention (cached-free LRU) and with the
   content-addressed host store.  Outputs are asserted byte-identical
   to retention-off paged and dense; wave 2+ must feed at least the
   shared-prefix length fewer prefill tokens, and the swap variant must
   adopt blocks from the host store.

6. **Fleet routing sweep** (``fleet_sweep``): R independent cloud
   replicas behind a ``ReplicaRouter``, serving streams that share a
   system prompt, once per routing policy on a fresh fleet.  Outputs
   are asserted byte-identical across all policies and to a
   single-engine run; prefix-affinity must feed fewer total prefill
   tokens than round-robin (it concentrates the shared prefix on the
   replica already holding it, round-robin re-prefills it once per
   replica).

7. **Stall-attribution sweep** (``stall_sweep``): the unified tracer
   (serving/trace.py) attached at high concurrency on a tight pool,
   host swap off and on.  Outputs are asserted byte-identical to an
   untraced run (tracing is passive); reported per row are the
   exclusive stall buckets and their shares of total stream wall time,
   asserted to sum to it.

Usage:
  PYTHONPATH=src:. python -m benchmarks.scale_bench [--fast] \
      [--streams 1,2,4,8] [--concurrency 8,32,128] \
      [--shared-streams 4,8] [--prefix-blocks 4] \
      [--preempt-concurrency 8,32,128] \
      [--cross-waves 3] [--cross-streams 2] \
      [--fleet-replicas 4] [--fleet-streams 64] \
      [--stall-concurrency 8,32,128] \
      [--out benchmarks/BENCH_scale.json]

Skipped sweeps ('' as the list) keep their previously written section
in the output JSON, so one sweep can be refreshed without re-running
the others.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def run_sweep(streams=(1, 2, 4, 8), max_new: int = 32, slots: int = 8,
              budget_all: bool = True) -> dict:
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving import synergy as SY

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)
    eng = PC.make_engine(llm_cfg, llm_p, slots=slots)

    rows = []
    for n in streams:
        evalset = PC.eval_set(task, n, seed=17)
        prompts = [p for p, _ in evalset]

        t0 = time.time()
        r_seq = SY.run_synera(dev, eng, prompts, max_new, concurrency=1)
        t_seq = time.time() - t0
        seq = r_seq.extras["scheduler"]

        t0 = time.time()
        r_con = SY.run_synera(dev, eng, prompts, max_new,
                              concurrency=min(n, slots))
        t_con = time.time() - t0
        con = r_con.extras["scheduler"]

        assert r_con.outputs == r_seq.outputs, \
            "concurrent serving must not change greedy token streams"

        tbts = [m.tbt_ms for m in r_con.metrics]
        n_tokens = sum(len(m.tokens) for m in r_con.metrics)
        rows.append(dict(
            streams=n,
            occupancy=con["mean_verify_occupancy"],
            max_occupancy=con["max_verify_occupancy"],
            packed_tokens_per_iter=con["mean_packed_tokens"],
            iterations=con["iterations"],
            iterations_sequential=seq["iterations"],
            makespan_ms=con["sim_ms"],
            makespan_sequential_ms=seq["sim_ms"],
            tbt_mean_ms=float(np.mean(tbts)),
            tbt_p95_ms=float(np.quantile(tbts, 0.95)),
            tbt_sequential_ms=r_seq.tbt_ms,
            cost=r_con.cost,
            tokens=n_tokens,
            wall_s_sequential=t_seq,
            wall_s_concurrent=t_con,
        ))
        print(f"streams={n:2d} occupancy={rows[-1]['occupancy']:.2f} "
              f"packed_tok/iter={rows[-1]['packed_tokens_per_iter']:.1f} "
              f"iters={rows[-1]['iterations']} "
              f"(seq {rows[-1]['iterations_sequential']}) "
              f"tbt={rows[-1]['tbt_mean_ms']:.1f}ms "
              f"p95={rows[-1]['tbt_p95_ms']:.1f}ms", flush=True)
    return dict(slots=slots, max_new=max_new, rows=rows)


def run_cache_sweep(concurrency=(8, 32, 128), max_new: int = 8,
                    slots: int = 8, block_size: int = 8) -> dict:
    """Dense vs paged cache at oversubscribed concurrency.

    Each stream count is served once on a dense engine and once on a
    paged engine (same slots; the paged pool is left at dense capacity —
    the saving reported is *peak blocks actually touched*, which is what
    a right-sized pool must hold).  Outputs are asserted identical.
    """
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving import synergy as SY

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)

    rows = []
    for n in concurrency:
        evalset = PC.eval_set(task, n, seed=23)
        prompts = [p for p, _ in evalset]

        eng_d = PC.make_engine(llm_cfg, llm_p, slots=slots)
        t0 = time.time()
        r_d = SY.run_synera(dev, eng_d, prompts, max_new, concurrency=n)
        t_d = time.time() - t0
        st_d = r_d.extras["scheduler"]

        eng_p = PC.make_engine(llm_cfg, llm_p, slots=slots,
                               cache_impl="paged", block_size=block_size)
        t0 = time.time()
        r_p = SY.run_synera(dev, eng_p, prompts, max_new, concurrency=n)
        t_p = time.time() - t0
        st_p = r_p.extras["scheduler"]

        assert r_p.outputs == r_d.outputs, \
            "paged serving must not change greedy token streams"

        tokens = sum(len(m.tokens) for m in r_p.metrics)
        # dense must reserve the full slots x s_max cache; a right-sized
        # paged pool holds the peak block usage
        dense_bytes = st_d["kv_cache_bytes"]
        paged_bytes = st_p["kv_bytes_peak"]
        rows.append(dict(
            concurrency=n,
            tokens=tokens,
            dense_cache_bytes=dense_bytes,
            paged_cache_bytes_peak=paged_bytes,
            dense_bytes_per_token=dense_bytes / max(tokens, 1),
            paged_bytes_per_token=paged_bytes / max(tokens, 1),
            bytes_per_token_ratio=dense_bytes / max(paged_bytes, 1),
            peak_used_blocks=st_p["peak_used_blocks"],
            n_blocks=st_p["n_blocks"],
            preemptions=st_p["preemptions"],
            makespan_dense_ms=st_d["sim_ms"],
            makespan_paged_ms=st_p["sim_ms"],
            wall_s_dense=t_d,
            wall_s_paged=t_p,
        ))
        print(f"concurrency={n:3d} dense={dense_bytes/2**20:.1f}MiB "
              f"paged_peak={paged_bytes/2**20:.1f}MiB "
              f"({rows[-1]['bytes_per_token_ratio']:.1f}x) "
              f"blocks={st_p['peak_used_blocks']}/{st_p['n_blocks']} "
              f"preempt={st_p['preemptions']}", flush=True)
    return dict(slots=slots, max_new=max_new, block_size=block_size,
                rows=rows)


def run_shared_prefix_sweep(streams=(4, 8), max_new: int = 8,
                            slots: int = 8, block_size: int = 8,
                            prefix_blocks: int = 4,
                            suffix_tokens: int = 8) -> dict:
    """Prefix-sharing on/off at full-slot concurrency with a common
    system prompt of ``prefix_blocks`` full blocks per stream.

    The sharing run must dedupe exactly those blocks across the
    co-resident streams: peak pool usage drops by
    ``prefix_blocks x (streams - 1)`` (asserted as a >= bound; outputs
    asserted byte-identical to the non-sharing paged run and to dense).
    """
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving import synergy as SY

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)
    rng = np.random.default_rng(37)
    vocab = slm_cfg.vocab
    common = [int(t) for t in rng.integers(1, vocab - 1,
                                           prefix_blocks * block_size)]

    rows = []
    for n in streams:
        prompts = [common + [int(t) for t in rng.integers(1, vocab - 1,
                                                          suffix_tokens)]
                   for _ in range(n)]
        conc = min(n, slots)

        eng_d = PC.make_engine(llm_cfg, llm_p, slots=slots)
        r_d = SY.run_synera(dev, eng_d, prompts, max_new, concurrency=conc)

        eng_off = PC.make_engine(llm_cfg, llm_p, slots=slots,
                                 cache_impl="paged", block_size=block_size)
        t0 = time.time()
        r_off = SY.run_synera(dev, eng_off, prompts, max_new,
                              concurrency=conc)
        t_off = time.time() - t0
        st_off = r_off.extras["scheduler"]

        eng_on = PC.make_engine(llm_cfg, llm_p, slots=slots,
                                cache_impl="paged", block_size=block_size,
                                share_prefix=True)
        t0 = time.time()
        r_on = SY.run_synera(dev, eng_on, prompts, max_new,
                             concurrency=conc)
        t_on = time.time() - t0
        st_on = r_on.extras["scheduler"]

        assert r_off.outputs == r_d.outputs, \
            "paged serving must not change greedy token streams"
        assert r_on.outputs == r_d.outputs, \
            "prefix sharing must not change greedy token streams"
        saved = st_off["peak_used_blocks"] - st_on["peak_used_blocks"]
        assert saved >= prefix_blocks * (conc - 1), (st_off, st_on)

        rows.append(dict(
            streams=n,
            concurrency=conc,
            prefix_tokens=len(common),
            prefix_blocks=prefix_blocks,
            peak_used_blocks_noshare=st_off["peak_used_blocks"],
            peak_used_blocks_share=st_on["peak_used_blocks"],
            saved_peak_blocks=saved,
            dedupe_hit_blocks=st_on["dedupe_hit_blocks"],
            cow_copies=st_on["cow_copies"],
            kv_bytes_peak_noshare=st_off["kv_bytes_peak"],
            kv_bytes_peak_share=st_on["kv_bytes_peak"],
            prefill_iterations=st_on["prefill_iterations"],
            makespan_noshare_ms=st_off["sim_ms"],
            makespan_share_ms=st_on["sim_ms"],
            wall_s_noshare=t_off,
            wall_s_share=t_on,
        ))
        print(f"streams={n:3d} peak_blocks {st_off['peak_used_blocks']}"
              f"->{st_on['peak_used_blocks']} (saved {saved}, "
              f">= {prefix_blocks * (conc - 1)} required) "
              f"dedupe={st_on['dedupe_hit_blocks']} "
              f"cow={st_on['cow_copies']}", flush=True)
    return dict(slots=slots, max_new=max_new, block_size=block_size,
                prefix_blocks=prefix_blocks, suffix_tokens=suffix_tokens,
                rows=rows)


def run_preempt_sweep(concurrency=(8, 32, 128), max_new: int = 6,
                      slots: int = 8, block_size: int = 8,
                      long_tokens: int = 40, short_tokens: int = 8) -> dict:
    """Preemption pressure: recompute vs swap vs slo-aware eviction on a
    pool sized so concurrent streams force evictions (ISSUE 5).

    For each stream count and prompt profile (short vs long prefixes)
    the same request set is served four ways on fresh pool state:

    * roomy pool (dense-capacity blocks, no preemption — the reference);
    * tight pool, recompute-eviction (victims refeed their whole prefix);
    * tight pool, host swap tier (victims park in host RAM, restore
      bit-identical, refeed nothing);
    * tight pool, swap + slo-aware victim selection (every other stream
      carries a deadline; no-SLO streams absorb the evictions).

    Outputs are asserted byte-identical across all four.  The headline
    column is ``preempted_refed_tokens``: recompute pays the re-prefill
    (large for long prefixes), swap pays only the modeled D2H+H2D bytes.
    """
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving.swap import StreamSLO
    from repro.serving import synergy as SY

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)
    rng = np.random.default_rng(43)
    vocab = slm_cfg.vocab

    def engines_for(plen):
        # a tight pool: ~3 live streams' worth of blocks on 8 slots
        per_stream = -(-(plen + max_new + 8) // block_size) + 1
        pool = 3 * per_stream
        mk = lambda **kw: PC.make_engine(llm_cfg, llm_p, slots=slots,
                                         cache_impl="paged",
                                         block_size=block_size, **kw)
        # the slo config differs only in scheduler policy/budgets, so it
        # shares the swap engine (drained between runs; jit is per-engine)
        swap_eng = mk(pool_blocks=pool, swap=True)
        return pool, dict(recompute=mk(pool_blocks=pool),
                          swap=swap_eng, slo=swap_eng)

    eng_roomy = PC.make_engine(llm_cfg, llm_p, slots=slots,
                               cache_impl="paged", block_size=block_size)
    profiles = {p: engines_for(t)
                for p, t in (("short", short_tokens), ("long", long_tokens))}

    rows = []
    for n in concurrency:
        for profile, plen in (("short", short_tokens),
                              ("long", long_tokens)):
            prompts = [[int(t) for t in rng.integers(1, vocab - 1, plen)]
                       for _ in range(n)]
            pool, engs = profiles[profile]
            r_ref = SY.run_synera(dev, eng_roomy, prompts, max_new,
                                  concurrency=n)
            slos = [StreamSLO(deadline_ms=5e3) if i % 2 == 0 else None
                    for i in range(n)]
            row = dict(concurrency=n, profile=profile,
                       prompt_tokens=plen, pool_blocks=pool,
                       tokens=sum(len(m.tokens) for m in r_ref.metrics))
            for name, eng in engs.items():
                # engines are reused across rows but the swap byte
                # counters are engine-cumulative: report per-run deltas
                sw = eng.swap_manager
                out0 = sw.swap_out_bytes if sw else 0
                in0 = sw.swap_in_bytes if sw else 0
                t0 = time.time()
                r = SY.run_synera(
                    dev, eng, prompts, max_new, concurrency=n,
                    preempt_policy="slo-aware" if name == "slo" else None,
                    slos=slos if name == "slo" else None)
                wall = time.time() - t0
                st = r.extras["scheduler"]
                assert r.outputs == r_ref.outputs, \
                    f"{name} eviction must not change greedy token streams"
                row[name] = dict(
                    preemptions=st["preemptions"],
                    recompute_evictions=st["recompute_evictions"],
                    swap_evictions=st["swap_evictions"],
                    preempted_refed_tokens=st["preempted_refed_tokens"],
                    swap_out_bytes=st["swap_out_bytes"] - out0,
                    swap_in_bytes=st["swap_in_bytes"] - in0,
                    makespan_ms=st["sim_ms"],
                    wall_s=wall)
            rows.append(row)
            print(f"conc={n:3d} {profile:5s} pool={pool:3d} "
                  f"refed recompute={row['recompute']['preempted_refed_tokens']} "
                  f"swap={row['swap']['preempted_refed_tokens']} "
                  f"slo={row['slo']['preempted_refed_tokens']} "
                  f"(swap_ev {row['swap']['swap_evictions']}, "
                  f"slo_ev {row['slo']['swap_evictions']})", flush=True)
    return dict(slots=slots, max_new=max_new, block_size=block_size,
                long_tokens=long_tokens, short_tokens=short_tokens,
                rows=rows)


def run_cross_session_sweep(waves: int = 3, streams: int = 2,
                            max_new: int = 6, slots: int = 4,
                            block_size: int = 8,
                            prefix_blocks: int = 4,
                            suffix_tokens: int = 8) -> dict:
    """Cross-session prefix reuse (ISSUE 8): N sequential,
    *non-overlapping* waves of sessions sharing a system prompt.

    Four variants serve every wave on persistent engine state:

    * dense (the oracle) and paged retention-off (each wave re-prefills
      the full system prompt);
    * ``retain_prefix``: wave 1's released chain parks on the
      cached-free LRU and wave 2+ revives it on-device;
    * ``share_prefix + swap + host_dedupe`` (retention off): wave 1's
      chain is demoted to the content-addressed host store on release
      and wave 2+ *adopts* it back by H2D scatter.

    Asserted per wave: outputs byte-identical across all four; from
    wave 2 on, both caching variants feed at least the shared-prefix
    length fewer prefill tokens than retention-off, and the swap
    variant's adoptions come from the host store (zero live sharers).
    """
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving import synergy as SY

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)
    rng = np.random.default_rng(53)
    vocab = slm_cfg.vocab
    prefix_len = prefix_blocks * block_size
    common = [int(t) for t in rng.integers(1, vocab - 1, prefix_len)]

    mk = lambda **kw: PC.make_engine(llm_cfg, llm_p, slots=slots,
                                     cache_impl="paged",
                                     block_size=block_size, **kw)
    eng_dense = PC.make_engine(llm_cfg, llm_p, slots=slots)
    eng_off = mk()
    eng_ret = mk(retain_prefix=True)
    eng_hsw = mk(share_prefix=True, swap=True, host_dedupe=True)

    rows, adopted_prev = [], 0
    for w in range(1, waves + 1):
        prompts = [common + [int(t) for t in rng.integers(
                       1, vocab - 1, suffix_tokens)]
                   for _ in range(streams)]
        r_d = SY.run_synera(dev, eng_dense, prompts, max_new,
                            concurrency=streams)
        r_off = SY.run_synera(dev, eng_off, prompts, max_new,
                              concurrency=streams)
        r_ret = SY.run_synera(dev, eng_ret, prompts, max_new,
                              concurrency=streams)
        r_hsw = SY.run_synera(dev, eng_hsw, prompts, max_new,
                              concurrency=streams)
        for name, r in (("paged", r_off), ("retain", r_ret),
                        ("host_swap", r_hsw)):
            assert r.outputs == r_d.outputs, \
                f"{name} wave {w} must not change greedy token streams"
        fed_off = r_off.extras["scheduler"]["prefill_fed_tokens"]
        fed_ret = r_ret.extras["scheduler"]["prefill_fed_tokens"]
        fed_hsw = r_hsw.extras["scheduler"]["prefill_fed_tokens"]
        adopted = eng_hsw.swap_manager.host_adopted_blocks
        row = dict(wave=w, streams=streams, prefix_tokens=prefix_len,
                   prefill_fed_tokens_off=fed_off,
                   prefill_fed_tokens_retain=fed_ret,
                   prefill_fed_tokens_host_swap=fed_hsw,
                   revived_blocks=eng_ret.allocator.revived_blocks,
                   tail_shared_tokens=(
                       eng_ret.allocator.tail_shared_tokens),
                   host_adopted_blocks_wave=adopted - adopted_prev,
                   host_store_blocks=eng_hsw.swap_manager.host_store_blocks)
        adopted_prev = adopted
        if w >= 2:
            assert fed_off - fed_ret >= prefix_len, row
            assert fed_off - fed_hsw >= prefix_len, row
            assert row["host_adopted_blocks_wave"] > 0, row
        rows.append(row)
        print(f"wave={w} fed off={fed_off} retain={fed_ret} "
              f"host_swap={fed_hsw} "
              f"adopted={row['host_adopted_blocks_wave']} "
              f"store={row['host_store_blocks']}", flush=True)
    return dict(waves=waves, streams=streams, max_new=max_new,
                slots=slots, block_size=block_size,
                prefix_blocks=prefix_blocks, suffix_tokens=suffix_tokens,
                rows=rows)


def run_fleet_sweep(replicas=(4,), streams: int = 64, max_new: int = 4,
                    slots: int = 4, block_size: int = 8,
                    prefix_blocks: int = 4, suffix_tokens: int = 8,
                    concurrency: int = 8) -> dict:
    """Multi-replica routing (ISSUE 9): R independent cloud replicas
    behind a ``ReplicaRouter``, all streams sharing a system prompt of
    ``prefix_blocks`` full blocks.

    Workload shape: one seed stream, then the remaining streams
    admitted ``concurrency`` at a time — so every post-seed placement
    probes a fleet that already holds the prefix somewhere.  Each
    policy gets a FRESH fleet of retain+share_prefix paged engines.

    Asserted: outputs byte-identical across all policies and to a
    single-engine run; prefix-affinity feeds strictly fewer total
    prefill tokens than round-robin.
    """
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving import synergy as SY
    from repro.serving.router import ROUTE_POLICIES, ReplicaRouter
    from repro.serving.server import build_fleet

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)
    rng = np.random.default_rng(61)
    vocab = slm_cfg.vocab
    common = [int(t) for t in rng.integers(1, vocab - 1,
                                           prefix_blocks * block_size)]
    prompts = [common + [int(t) for t in rng.integers(1, vocab - 1,
                                                      suffix_tokens)]
               for _ in range(streams)]

    mk = lambda: PC.make_engine(llm_cfg, llm_p, slots=slots,
                                cache_impl="paged", block_size=block_size,
                                share_prefix=True, retain_prefix=True)

    r_ref = SY.run_synera(dev, mk(), prompts, max_new, concurrency=1)
    ref_out = [[int(t) for t in o] for o in r_ref.outputs]

    rows = []
    for n_rep in replicas:
        row = dict(replicas=n_rep, streams=streams,
                   prefix_tokens=len(common), concurrency=concurrency)
        for policy in ROUTE_POLICIES:
            router = ReplicaRouter(
                build_fleet(dev, [mk() for _ in range(n_rep)]),
                policy=policy)
            t0 = time.time()
            metrics = router.serve(prompts[:1], max_new, concurrency=1)
            metrics += router.serve(prompts[1:], max_new,
                                    concurrency=concurrency)
            wall = time.time() - t0
            outs = [[int(t) for t in m.tokens] for m in metrics]
            assert outs == ref_out, \
                f"{policy} routing must not change greedy token streams"
            st = router.stats()
            touched = {router.owner[id(s)] for s in router.sessions}
            row[policy] = dict(
                prefill_fed_tokens=st["prefill_fed_tokens"],
                affinity_hits=st["affinity_hits"],
                revived_blocks=st["revived_blocks"],
                dedupe_hit_blocks=st["dedupe_hit_blocks"],
                replicas_touched=len(touched),
                degraded_streams=st["degraded_streams"],
                wall_s=wall)
        fed_aff = row["prefix-affinity"]["prefill_fed_tokens"]
        fed_rr = row["round-robin"]["prefill_fed_tokens"]
        assert fed_aff < fed_rr, row
        rows.append(row)
        print(f"replicas={n_rep} streams={streams} prefill_fed "
              f"rr={fed_rr} ll="
              f"{row['least-loaded']['prefill_fed_tokens']} "
              f"affinity={fed_aff} "
              f"(hits={row['prefix-affinity']['affinity_hits']}, "
              f"touched {row['prefix-affinity']['replicas_touched']} vs "
              f"rr {row['round-robin']['replicas_touched']})", flush=True)
    return dict(streams=streams, max_new=max_new, slots=slots,
                block_size=block_size, prefix_blocks=prefix_blocks,
                suffix_tokens=suffix_tokens, rows=rows)


def run_stall_sweep(concurrency=(8, 32, 128), max_new: int = 6,
                    slots: int = 8, block_size: int = 8) -> dict:
    """Stall-time attribution under load (ISSUE 10): each stream count
    is served with the unified tracer attached (serving/trace.py) on a
    paged pool tight enough to force queueing/preemption, with the host
    swap tier off and on.

    Reported per row: the fleet's exclusive stall buckets (device /
    cloud / link / queue / batch_wait / swap / preempted / other) and
    their shares of total stream wall time — asserted to sum to it
    within float tolerance.  Tracing is passive: outputs are asserted
    byte-identical to an untraced run on identical engine state.
    """
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving import synergy as SY

    BUCKETS = ("device", "cloud", "link", "queue", "batch_wait", "swap",
               "preempted", "other")
    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)

    rows = []
    for n in concurrency:
        evalset = PC.eval_set(task, n, seed=31)
        prompts = [p for p, _ in evalset]
        plen = max(len(p) for p in prompts)
        # a tight pool: ~3 live streams' worth of blocks (preempt_sweep
        # sizing) so oversubscription shows up in the wait/swap buckets
        per_stream = -(-(plen + max_new + 8) // block_size) + 1
        pool = 3 * per_stream
        mk = lambda **kw: PC.make_engine(llm_cfg, llm_p, slots=slots,
                                         cache_impl="paged",
                                         block_size=block_size,
                                         pool_blocks=pool, **kw)
        # outputs are invariant across swap on/off (tested elsewhere),
        # so one untraced run is the byte-identity reference for both
        r_ref = SY.run_synera(dev, mk(), prompts, max_new, concurrency=n)
        for swap in (False, True):
            t0 = time.time()
            r = SY.run_synera(dev, mk(swap=swap), prompts, max_new,
                              concurrency=n, trace=True)
            wall_s = time.time() - t0
            assert r.outputs == r_ref.outputs, \
                "tracing must not change greedy token streams"
            st = r.extras["scheduler"]
            wall = st["stall_wall_ms"]
            buckets = {b: st[f"stall_{b}_ms"] for b in BUCKETS}
            total = sum(buckets.values())
            assert abs(total - wall) <= 1e-6 * max(1.0, wall), \
                (total, wall)
            rows.append(dict(
                concurrency=n, swap=swap, pool_blocks=pool,
                stall_wall_ms=wall,
                buckets_ms=buckets,
                bucket_shares={b: v / max(wall, 1e-9)
                               for b, v in buckets.items()},
                preemptions=st["preemptions"],
                swap_evictions=st["swap_evictions"],
                makespan_ms=st["sim_ms"],
                wall_s=wall_s))
            shares = rows[-1]["bucket_shares"]
            print(f"conc={n:3d} swap={int(swap)} pool={pool:3d} "
                  f"device={shares['device']:.0%} "
                  f"cloud={shares['cloud']:.0%} "
                  f"wait={shares['batch_wait']:.0%} "
                  f"queue={shares['queue']:.0%} "
                  f"swap={shares['swap']:.0%} "
                  f"preempt={shares['preempted']:.0%}", flush=True)
    return dict(slots=slots, max_new=max_new, block_size=block_size,
                rows=rows)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--streams", default="1,2,4,8")
    ap.add_argument("--concurrency", default="8,32,128",
                    help="stream counts for the dense-vs-paged cache "
                         "sweep ('' to skip)")
    ap.add_argument("--shared-streams", default="4,8",
                    help="stream counts for the shared-prefix sweep "
                         "('' to skip)")
    ap.add_argument("--preempt-concurrency", default="8,32,128",
                    help="stream counts for the preemption-pressure "
                         "recompute/swap/slo sweep ('' to skip)")
    ap.add_argument("--prefix-blocks", type=int, default=4,
                    help="common system-prefix length in full KV blocks")
    ap.add_argument("--cross-waves", default="3",
                    help="sequential non-overlapping waves for the "
                         "cross-session reuse sweep ('' to skip)")
    ap.add_argument("--cross-streams", type=int, default=2,
                    help="sessions per wave in the cross-session sweep")
    ap.add_argument("--fleet-replicas", default="4",
                    help="replica counts for the multi-replica routing "
                         "sweep ('' to skip)")
    ap.add_argument("--fleet-streams", type=int, default=64,
                    help="streams per fleet-sweep row")
    ap.add_argument("--stall-concurrency", default="8,32,128",
                    help="stream counts for the traced stall-"
                         "attribution sweep, swap off/on per count "
                         "('' to skip)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--out", default="benchmarks/BENCH_scale.json")
    args = ap.parse_args()
    # skipped sweeps keep their previously written section
    res = {}
    try:
        with open(args.out) as f:
            res = json.load(f)
    except (OSError, ValueError):
        pass
    if args.streams:
        streams = tuple(int(s) for s in args.streams.split(","))
        res.update(run_sweep(streams=streams,
                             max_new=16 if args.fast else 32,
                             slots=args.slots))
    if args.concurrency:
        conc = tuple(int(s) for s in args.concurrency.split(","))
        res["cache_sweep"] = run_cache_sweep(
            concurrency=conc, max_new=4 if args.fast else 8,
            slots=args.slots, block_size=args.block_size)
    if args.shared_streams:
        shared = tuple(int(s) for s in args.shared_streams.split(","))
        res["shared_prefix_sweep"] = run_shared_prefix_sweep(
            streams=shared, max_new=4 if args.fast else 8,
            slots=args.slots, block_size=args.block_size,
            prefix_blocks=args.prefix_blocks)
    if args.preempt_concurrency:
        conc = tuple(int(s) for s in args.preempt_concurrency.split(","))
        res["preempt_sweep"] = run_preempt_sweep(
            concurrency=conc, max_new=4 if args.fast else 6,
            slots=args.slots, block_size=args.block_size)
    if args.cross_waves:
        res["cross_session_sweep"] = run_cross_session_sweep(
            waves=int(args.cross_waves), streams=args.cross_streams,
            max_new=4 if args.fast else 6,
            block_size=args.block_size,
            prefix_blocks=args.prefix_blocks)
    if args.fleet_replicas:
        reps = tuple(int(s) for s in args.fleet_replicas.split(","))
        res["fleet_sweep"] = run_fleet_sweep(
            replicas=reps,
            streams=16 if args.fast else args.fleet_streams,
            block_size=args.block_size,
            prefix_blocks=args.prefix_blocks)
    if args.stall_concurrency:
        conc = tuple(int(s) for s in args.stall_concurrency.split(","))
        res["stall_sweep"] = run_stall_sweep(
            concurrency=conc, max_new=4 if args.fast else 6,
            slots=args.slots, block_size=args.block_size)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
