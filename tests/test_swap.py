"""Host-memory KV swap tier + policy-driven preemption (ISSUE 5).

The headline property: with ``swap=True`` and a pool forced dry,
greedy token streams are byte-identical to the non-preempted dense and
paged runs under all three victim policies (``youngest``,
``most-blocks``, ``slo-aware``) — the swapped blocks are restored
bit-for-bit, so preemption disposition can never change outputs, only
modeled time.

Layers covered:

* ``HostSwapManager`` units — plan (shared-lead detection, host
  capacity), swap-out freeing exactly the unshared blocks, swap-in
  re-adoption of a still-shared lead, swap-in degradation when the
  share expired (the sibling died while the victim was on the host);
* disposition policy — a crippled host link makes recompute the
  modeled winner (swap enabled but unused), a tiny host store forces
  the recompute fallback;
* serving-level identity across {no-preemption, recompute, swap} and
  across victim policies (hypothesis property), forced
  swap-out-while-shared, and the scheduler-level share-expiry rewind.

Engines are module-scoped fixtures (jitted steps are expensive to
recompile; released slots are fully reset and the swap store drains
with its sessions, so reuse is safe).
"""
import numpy as np
import pytest

import jax

from hypothesis import example, given, settings, strategies as st

from repro.configs.synera_pair import tiny_pair
from repro.core.offload import OffloadPolicy
from repro.models import model as M
from repro.serving.device import DeviceRuntime
from repro.serving.engine import CloudEngine
from repro.serving.link import CloudLatencyModel
from repro.serving.scheduler import (PrefillRequest, VerifyRequest,
                                     VerificationAwareScheduler)
from repro.serving.swap import PREEMPT_POLICIES, StreamSLO
from repro.serving import synergy as SY

S_MAX = 256


@pytest.fixture(scope="module")
def pair():
    slm_cfg, llm_cfg = tiny_pair(vocab=64)
    slm_p = M.init_params(slm_cfg, jax.random.PRNGKey(0))
    llm_p = M.init_params(llm_cfg, jax.random.PRNGKey(1))
    return slm_cfg, slm_p, llm_cfg, llm_p


@pytest.fixture(scope="module")
def dev(pair):
    slm_cfg, slm_p, _, _ = pair
    return DeviceRuntime(slm_cfg, slm_p, s_max=S_MAX, gamma=4, seed=0,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False, use_pi=False)


@pytest.fixture(scope="module")
def eng_dense(pair):
    _, _, llm_cfg, llm_p = pair
    return CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=S_MAX)


@pytest.fixture(scope="module")
def eng_recompute(pair):
    """Tight pool, no swap tier: recompute-eviction under pressure."""
    _, _, llm_cfg, llm_p = pair
    return CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=S_MAX,
                       cache_impl="paged", block_size=4, pool_blocks=11)


@pytest.fixture(scope="module")
def eng_swap(pair):
    """Same tight pool with the host swap tier enabled."""
    _, _, llm_cfg, llm_p = pair
    return CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=S_MAX,
                       cache_impl="paged", block_size=4, pool_blocks=11,
                       swap=True)


def _prompts(lens, seed=5):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 60, size=max(L, 2))]
            for L in lens]


def _drained(eng):
    assert eng.allocator.used_blocks == 0
    if eng.swap_manager is not None:
        assert eng.swap_manager.swapped_blocks == 0


# ---------------------------------------------------------------------------
# Engine/manager units
# ---------------------------------------------------------------------------

def _prefill_slot(eng, slot, P):
    B = eng.max_slots
    m = eng.alloc_prompt(slot, P)
    t = np.zeros((B, len(P)), np.int32)
    p = np.full((B, len(P)), -1, np.int32)
    t[slot, m:] = P[m:]
    p[slot, m:] = np.arange(m, len(P))
    eng.prefill(t, p)
    return m


def test_swap_requires_paged(pair):
    _, _, llm_cfg, llm_p = pair
    with pytest.raises(ValueError, match="paged"):
        CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=64, swap=True)


def test_manager_roundtrip_restores_bit_identical(pair):
    """Swap a slot out and back in; a decode afterwards matches a
    never-swapped engine bit-for-bit (the pool content was restored
    exactly, through fresh blocks)."""
    _, _, llm_cfg, llm_p = pair

    def mk(swap):
        return CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=64,
                           cache_impl="paged", block_size=4, swap=swap)

    P = _prompts([12], seed=3)[0]
    outs = []
    for swap in (True, False):
        eng = mk(swap)
        _prefill_slot(eng, 0, P)
        if swap:
            sw = eng.swap_manager
            assert sw.plan(0) == (0, 3, 3 * eng.block_bytes())
            moved = sw.swap_out(0, P, len(P))
            assert moved == 3 * eng.block_bytes()
            assert eng.allocator.used_blocks == 0
            assert sw.swapped_blocks == 3
            assert sw.swap_in(0) == (len(P), moved)
            assert sw.swapped_blocks == 0
            assert eng.allocator.used_blocks == 3
        td = np.zeros((2, 1), np.int32)
        pd = np.full((2, 1), -1, np.int32)
        td[0, 0], pd[0, 0] = 5, len(P)
        outs.append(eng.decode(td, pd))
        eng.reset_slot(0)
        _drained(eng)
    assert np.array_equal(outs[0].token_id[0], outs[1].token_id[0])
    assert np.array_equal(outs[0].topk_idx[0], outs[1].topk_idx[0])
    assert np.array_equal(outs[0].topk_val[0], outs[1].topk_val[0])


def test_manager_shared_lead_drops_ref_and_readopts(pair):
    """Swapping a victim that rides on shared blocks never moves them:
    the victim drops its reference (the sibling keeps reading them) and
    re-adopts from the index at swap-in."""
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=64,
                      cache_impl="paged", block_size=4, share_prefix=True,
                      swap=True)
    a, sw = eng.allocator, eng.swap_manager
    P = _prompts([12], seed=7)[0]
    _prefill_slot(eng, 0, P)
    # adopts 2 leading blocks + 3 tail rows of the third (len-1 cap)
    m1 = _prefill_slot(eng, 1, P)
    assert m1 == 11 and a.shared_blocks == 2
    lead, n_swap, _ = sw.plan(1)
    assert (lead, n_swap) == (2, 1)        # only the private tail moves
    used0 = a.used_blocks
    sw.swap_out(1, P, len(P))
    assert a.used_blocks == used0 - 1      # shared lead stayed in-pool
    assert all(int(a.ref[int(a.table[0, j])]) == 1 for j in range(2))
    frontier, _ = sw.swap_in(1)
    assert frontier == len(P)
    assert a.shared_blocks == 2            # lead re-adopted (ref back to 2)
    eng.reset_slot(0)
    eng.reset_slot(1)
    _drained(eng)


def test_manager_swap_in_after_share_expired(pair):
    """If the sibling dies while the victim is on the host, the shared
    lead leaves the prefix index with it — swap-in must report the
    expiry (None) instead of restoring a stream missing its prefix."""
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=64,
                      cache_impl="paged", block_size=4, share_prefix=True,
                      swap=True)
    sw = eng.swap_manager
    P = _prompts([12], seed=9)[0]
    _prefill_slot(eng, 0, P)
    assert _prefill_slot(eng, 1, P) == 11  # 2 blocks + 3 tail rows
    sw.swap_out(1, P, len(P))
    eng.reset_slot(0)                      # sibling dies: share expires
    assert sw.swap_in(1) is None
    assert sw.expired_shares == 1
    assert sw.swapped_blocks == 0          # payload dropped
    _drained(eng)


def test_manager_host_capacity_gates_swap(pair):
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=64,
                      cache_impl="paged", block_size=4, swap=True,
                      host_swap_blocks=2)
    sw = eng.swap_manager
    P = _prompts([12], seed=11)[0]         # 3 blocks > capacity 2
    _prefill_slot(eng, 0, P)
    assert sw.plan(0) is None
    assert sw.swap_out(0, P, len(P)) is None
    eng.reset_slot(0)
    _drained(eng)


# ---------------------------------------------------------------------------
# Scheduler-level share expiry (degrade to recompute)
# ---------------------------------------------------------------------------

def test_scheduler_rewinds_on_share_expiry(pair):
    """_swap_in_ready: a swapped stream whose shared lead expired is
    rewound (frontier 0, pending requests refeed from scratch) and
    counted, instead of being restored with a hole in its prefix."""
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=4, s_max=64,
                      cache_impl="paged", block_size=4, share_prefix=True,
                      swap=True)
    sched = VerificationAwareScheduler(eng, chunk=8)
    P = _prompts([12], seed=13)[0]
    sched.submit_prefill(PrefillRequest(1, np.asarray(P)))
    sched.submit_prefill(PrefillRequest(2, np.asarray(P)))
    evs = sched.run_iteration()
    slots = {e.req_id: e.slot for e in evs}
    victim = slots[2]
    # evict the adopter to the host, then kill the sibling
    moved = eng.swap_manager.swap_out(victim, P,
                                      int(sched.cloud_len[victim]))
    assert moved is not None
    assert sched._slot_swapped(victim)
    sched.release_slot(slots[1])
    # a pending verify request for the swapped stream
    seq = np.asarray(P + [7, 8], np.int64)
    req = VerifyRequest(3, victim, uncached=seq[len(P):], draft=seq[-1:],
                        q_sparse=[], seq=seq)
    req.start_pos = int(sched.cloud_len[victim])
    sched.verify_q.append(req)
    sched._swap_in_ready()
    assert sched.swap_expirations == 1
    assert not sched._slot_swapped(victim)
    assert int(sched.cloud_len[victim]) == 0
    assert req.start_pos == 0 and req.fed == 0
    assert np.array_equal(req.uncached, seq)   # from-scratch partial prefill
    sched.release_slot(victim)
    _drained(eng)


def test_admission_reserves_blocks_for_swapped_head(pair):
    """Fresh prompt admissions must not consume the blocks a waiting
    swapped stream needs to return — otherwise a continuous arrival
    stream could eat every freed block the moment it appears and
    starve the swapped stream indefinitely."""
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=4, s_max=64,
                      cache_impl="paged", block_size=4, pool_blocks=9,
                      swap=True)
    a = eng.allocator
    sched = VerificationAwareScheduler(eng, chunk=8)
    P = _prompts([16, 12, 8], seed=19)
    sched.submit_prefill(PrefillRequest(1, np.asarray(P[0])))  # 4 blocks
    sched.submit_prefill(PrefillRequest(2, np.asarray(P[1])))  # 3 blocks
    evs = sched.run_iteration()
    slots = {e.req_id: e.slot for e in evs}
    # park stream 2 on the host (needs 3 blocks to come back) ...
    assert eng.swap_manager.swap_out(slots[2], P[1],
                                     int(sched.cloud_len[slots[2]])) \
        is not None
    # ... and let stream 1 grow into the freed space (verify growth),
    # leaving 2 free: NOT enough for the head to return
    assert a.extend(slots[1], 28)
    eng._tables_dirty = True
    eng._sync_tables()
    assert a.free_blocks == 2
    assert sched._swap_in_reserve() == 3
    # a fresh 2-block prompt WOULD fit the 2 free blocks, but they are
    # spoken for: it must queue, not starve the swapped head
    sched.submit_prefill(PrefillRequest(3, np.asarray(P[2])))
    assert sched.run_iteration() == []
    assert len(sched.prefill_q) == 1
    assert sched._slot_swapped(slots[2])
    # stream 1 exits: the head returns FIRST, then the prompt admits
    sched.release_slot(slots[1])
    evs = sched.run_iteration()
    assert not sched._slot_swapped(slots[2])
    assert int(sched.cloud_len[slots[2]]) == len(P[1])
    assert [e.req_id for e in evs] == [3]
    sched.release_slot(slots[2])
    for s in range(eng.max_slots):
        if a.n_blocks_of[s] > 0:
            sched.release_slot(s)
    _drained(eng)


# ---------------------------------------------------------------------------
# Disposition policy
# ---------------------------------------------------------------------------

def test_slow_host_link_prefers_recompute(dev, eng_dense, pair):
    """The disposition is a modeled-cost comparison, not a hard switch:
    with a crippled host link the D2H+H2D round trip loses to the
    re-prefill and the scheduler recomputes even though swap is on."""
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=S_MAX,
                      cache_impl="paged", block_size=4, pool_blocks=11,
                      swap=True)
    lat = CloudLatencyModel(host_link_gbps=1e-7)   # ~10 s per KB
    prompts = _prompts([8, 8, 8, 8], seed=29)
    r_ref = SY.run_synera(dev, eng_dense, prompts, 12, concurrency=1)
    r = SY.run_synera(dev, eng, prompts, 12, concurrency=4, latency=lat)
    assert r.outputs == r_ref.outputs
    st_ = r.extras["scheduler"]
    assert st_["swap_evictions"] == 0
    assert st_["recompute_evictions"] >= 1
    _drained(eng)


def test_tiny_host_store_falls_back_to_recompute(dev, eng_dense, pair):
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=S_MAX,
                      cache_impl="paged", block_size=4, pool_blocks=11,
                      swap=True, host_swap_blocks=1)
    prompts = _prompts([8, 8, 8, 8], seed=29)
    r_ref = SY.run_synera(dev, eng_dense, prompts, 12, concurrency=1)
    r = SY.run_synera(dev, eng, prompts, 12, concurrency=4)
    assert r.outputs == r_ref.outputs
    st_ = r.extras["scheduler"]
    assert st_["swap_evictions"] == 0 and st_["recompute_evictions"] >= 1
    _drained(eng)


# ---------------------------------------------------------------------------
# Serving-level acceptance
# ---------------------------------------------------------------------------

def test_swap_recovers_stream_without_refeeding(dev, eng_dense, eng_swap,
                                                eng_recompute):
    """ISSUE 5 acceptance: a pool forced dry serves identically under
    recompute and swap, and swap refeeds (far) fewer tokens."""
    prompts = _prompts([8, 8, 8, 8], seed=29)
    r_ref = SY.run_synera(dev, eng_dense, prompts, 12, concurrency=1)
    r_re = SY.run_synera(dev, eng_recompute, prompts, 12, concurrency=4)
    r_sw = SY.run_synera(dev, eng_swap, prompts, 12, concurrency=4)
    assert r_re.outputs == r_ref.outputs
    assert r_sw.outputs == r_ref.outputs
    st_re = r_re.extras["scheduler"]
    st_sw = r_sw.extras["scheduler"]
    assert st_re["recompute_evictions"] >= 1 and st_re["swap_evictions"] == 0
    assert st_sw["swap_evictions"] >= 1
    assert st_sw["swap_out_bytes"] > 0
    assert st_sw["swap_in_bytes"] == st_sw["swap_out_bytes"]
    # the whole point: swapped streams come back without refeeding
    assert (st_sw["preempted_refed_tokens"]
            < st_re["preempted_refed_tokens"])
    _drained(eng_swap)
    _drained(eng_recompute)


def test_swap_while_shared_preserves_identity(dev, eng_dense, pair):
    """Forced swap-out of a stream riding on shared prefix blocks: the
    sibling keeps its blocks, the victim re-adopts on swap-in, outputs
    stay byte-identical to dense."""
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=S_MAX,
                      cache_impl="paged", block_size=4, pool_blocks=11,
                      share_prefix=True, swap=True)
    rng = np.random.default_rng(31)
    common = [int(t) for t in rng.integers(1, 60, 8)]
    prompts = [common + [int(t) for t in rng.integers(1, 60, 4)]
               for _ in range(4)]
    r_ref = SY.run_synera(dev, eng_dense, prompts, 12, concurrency=1)
    r = SY.run_synera(dev, eng, prompts, 12, concurrency=4)
    assert r.outputs == r_ref.outputs
    st_ = r.extras["scheduler"]
    assert st_["swap_evictions"] >= 1
    assert st_["dedupe_hit_blocks"] >= 1
    _drained(eng)


def test_slo_aware_spares_tight_deadline(pair, dev):
    """slo-aware victim selection: under pressure the stream with the
    most remaining slack (here: no SLO at all) is evicted, never the
    one racing a deadline."""
    _, _, llm_cfg, llm_p = pair
    eng = CloudEngine(llm_cfg, llm_p, max_slots=3, s_max=S_MAX,
                      cache_impl="paged", block_size=4, pool_blocks=16,
                      swap=True)
    sched = VerificationAwareScheduler(eng, chunk=8,
                                       preempt_policy="slo-aware")
    P = _prompts([12, 12, 12], seed=17)
    sched.submit_prefill(PrefillRequest(1, np.asarray(P[0])))
    sched.submit_prefill(PrefillRequest(
        2, np.asarray(P[1]), slo=StreamSLO(deadline_ms=1.0)))
    sched.submit_prefill(PrefillRequest(3, np.asarray(P[2])))
    evs = sched.run_iteration()
    slots = {e.req_id: e.slot for e in evs}
    # req 2's stream is deadline-bound; a no-SLO stream (infinite
    # slack) must be chosen instead
    assert slots[2] != slots[3]
    victim = sched._pick_victim()
    assert victim == slots[3]
    assert victim != slots[2]
    for s in slots.values():
        sched.release_slot(s)
    _drained(eng)


# ---------------------------------------------------------------------------
# Property: identity across dispositions and victim policies
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(4, 20), min_size=2, max_size=4),
       st.integers(0, len(PREEMPT_POLICIES) - 1),
       st.integers(0, 1))            # arrivals: together | staggered
# an older stream restarting with no blocks once starved the protected
# block holder into "paged KV pool deadlocked"
@example(lens=[4, 17, 13], pol_i=0, arr_i=0)
@settings(max_examples=5, deadline=None)
def test_streams_identical_across_dispositions(dev, eng_dense, eng_recompute,
                                               eng_swap, lens, pol_i, arr_i):
    """Greedy token streams are byte-identical across {no-preemption
    (dense), recompute-eviction, swap-eviction} and across victim
    policies, whatever the prompt lengths and arrival pattern."""
    policy = PREEMPT_POLICIES[pol_i]
    prompts = _prompts(lens, seed=sum(lens) + 13 * len(lens))
    arrivals = None if arr_i == 0 else [i * 350.0 for i
                                        in range(len(prompts))]
    r_ref = SY.run_synera(dev, eng_dense, prompts, 10, concurrency=1)
    r_re = SY.run_synera(dev, eng_recompute, prompts, 10,
                         concurrency=len(prompts), arrivals=arrivals,
                         preempt_policy=policy)
    r_sw = SY.run_synera(dev, eng_swap, prompts, 10,
                         concurrency=len(prompts), arrivals=arrivals,
                         preempt_policy=policy)
    assert r_re.outputs == r_ref.outputs
    assert r_sw.outputs == r_ref.outputs
    _drained(eng_swap)
    _drained(eng_recompute)
