"""Multi-replica fleet routing tests (serving/router.py).

The invariant under test everywhere: *placement never changes content*.
Greedy token streams are deterministic functions of tokens and
positions only, so whatever the router does — round-robin, load
balancing, prefix-affinity, replica death with failover, saturation
degrade — every stream must be byte-identical to the single-engine
paged oracle.

Engines are module-scoped where tests only read token streams (a
released slot is fully reset, so reuse is safe and avoids jit
recompiles); tests that assert absolute pool counters (prefix-affinity
effectiveness) or permanently poison an engine (replica kill) build
fresh ones.
"""
import numpy as np
import pytest

import jax
from hypothesis import given, settings, strategies as st

from repro.configs.synera_pair import tiny_pair
from repro.core.offload import OffloadPolicy
from repro.models import model as M
from repro.serving import synergy as SY
from repro.serving.device import DeviceRuntime
from repro.serving.engine import CloudEngine
from repro.serving.router import ROUTE_POLICIES, ReplicaRouter
from repro.serving.server import WAIT_CLOUD, build_fleet


@pytest.fixture(scope="module")
def pair():
    slm_cfg, llm_cfg = tiny_pair(vocab=64)
    slm_p = M.init_params(slm_cfg, jax.random.PRNGKey(0))
    llm_p = M.init_params(llm_cfg, jax.random.PRNGKey(1))
    return slm_cfg, slm_p, llm_cfg, llm_p


@pytest.fixture(scope="module")
def dev(pair):
    slm_cfg, slm_p, _, _ = pair
    return DeviceRuntime(slm_cfg, slm_p, s_max=256, gamma=4, seed=0,
                         policy=OffloadPolicy(mode="all"),
                         use_early_exit=False, use_pi=False)


def _mk_engine(pair, **kw):
    _, _, llm_cfg, llm_p = pair
    kw.setdefault("cache_impl", "paged")
    kw.setdefault("block_size", 16)
    kw.setdefault("share_prefix", True)
    return CloudEngine(llm_cfg, llm_p, max_slots=2, s_max=256, **kw)


@pytest.fixture(scope="module")
def fleet4(pair):
    """Four reusable paged prefix-sharing replicas (no retention, so a
    drained replica returns to pristine pool state between tests), each
    committed to a device as ``repro.launch.serve`` places them."""
    devices = jax.devices()
    return [_mk_engine(pair, device=devices[i % len(devices)])
            for i in range(4)]


def _prompts(n, length=8, shared=0, seed=5):
    rng = np.random.default_rng(seed)
    common = [int(t) for t in rng.integers(1, 60, 16)]
    out = []
    for i in range(n):
        suffix = [int(t) for t in rng.integers(1, 60, length)]
        out.append((common if i < shared else []) + suffix)
    return out


def _tokens(metrics):
    return [[int(t) for t in m.tokens] for m in metrics]


def _assert_pristine(eng):
    pool = eng.pool_stats
    assert pool["used_blocks"] == 0, pool
    assert (pool["free_blocks"] + pool["cached_free_blocks"]
            == pool["n_blocks"]), pool


# ---------------------------------------------------------------------------
# Identity property: policies x replica counts x arrivals x prefix overlap
# ---------------------------------------------------------------------------

_ORACLE_CACHE: dict = {}


def _oracle(dev, eng, prompts, max_new):
    key = (tuple(tuple(p) for p in prompts), max_new)
    if key not in _ORACLE_CACHE:
        r = SY.run_synera(dev, eng, prompts, max_new, concurrency=1)
        _ORACLE_CACHE[key] = [[int(t) for t in o] for o in r.outputs]
    return _ORACLE_CACHE[key]


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 2), st.integers(0, 2),
       st.integers(0, 3), st.integers(0, 2))
def test_routing_identity_property(dev, fleet4, pair,
                                   policy_i, rep_i, arr_seed, frac_i):
    """Every stream's tokens are byte-identical to the single-engine
    oracle across policy x replica count x arrival pattern x
    shared-prefix fraction."""
    policy = ROUTE_POLICIES[policy_i]
    n_rep = (1, 2, 4)[rep_i]
    n, max_new = 4, 8
    prompts = _prompts(n, shared=(0, n // 2, n)[frac_i])
    if arr_seed == 0:
        arrivals = None
    elif arr_seed == 1:
        arrivals = [0.0] * n
    else:
        rng = np.random.default_rng(arr_seed)
        arrivals = np.cumsum(rng.exponential(40.0, n)).tolist()
    want = _oracle(dev, fleet4[0], prompts, max_new)

    r = SY.run_synera_fleet(dev, fleet4[:n_rep], prompts, max_new,
                            policy=policy, concurrency=n,
                            arrivals=arrivals)
    got = [[int(t) for t in o] for o in r.outputs]
    assert got == want, (policy, n_rep, arr_seed, frac_i)
    stats = r.extras["scheduler"]
    assert stats["replicas"] == n_rep
    assert stats["route_policy"] == policy
    assert stats["completed_streams"] == n
    assert stats["degraded_streams"] == 0
    assert len(r.extras["replicas"]) == n_rep
    for i, d in enumerate(r.extras["replicas"]):
        assert d["replica"] == i and not d["dead"]
    for eng in fleet4[:n_rep]:
        _assert_pristine(eng)


def test_round_robin_rotates(dev, fleet4):
    """The identity oracle policy really is state-oblivious rotation."""
    prompts = _prompts(4)
    router = ReplicaRouter(build_fleet(dev, fleet4[:2]),
                           policy="round-robin")
    router.serve(prompts, 4, concurrency=4)
    owners = [router.owner[id(s)] for s in router.sessions]
    assert owners == [0, 1, 0, 1]


# ---------------------------------------------------------------------------
# Fault injection: replica death mid-verify
# ---------------------------------------------------------------------------

def test_replica_kill_mid_verify(dev, fleet4, pair):
    """Kill replica 0 while it has a verify in flight: its sessions are
    re-placed on the survivor as from-scratch prefills, finish with
    byte-identical outputs, and the survivor leaks no blocks.  The dead
    engine is poisoned — any further dispatch raises."""
    n, max_new = 4, 16
    prompts = _prompts(n, length=12, seed=11)
    want = _oracle(dev, fleet4[0], prompts, max_new)

    engines = [_mk_engine(pair), _mk_engine(pair)]
    router = ReplicaRouter(build_fleet(dev, engines), policy="round-robin")
    sess = [router.open_session(p, max_new) for p in prompts]
    for _ in range(400):
        router.step()
        if any(s.state == WAIT_CLOUD
               for s in router.replicas[0].sessions if not s.done):
            break
    else:
        pytest.fail("replica 0 never reached a mid-verify state")

    moved = router.kill_replica(0)
    assert moved >= 1
    assert router.kill_replica(0) == 0          # idempotent
    while router.step():
        pass

    assert _tokens([s.metrics for s in sess]) == want
    assert engines[0].dead
    with pytest.raises(RuntimeError, match="marked dead"):
        engines[0].feed(np.zeros((2, 4), np.int32),
                        np.full((2, 4), -1, np.int32))
    _assert_pristine(engines[1])                # survivor leaks nothing
    stats = router.stats()
    assert stats["rerouted_sessions"] == moved
    assert stats["dead_replicas"] == 1
    assert stats["completed_streams"] == n
    assert router.replica_stats(0)["dead"]
    assert not router.replica_stats(1)["dead"]


def test_kill_before_first_step_reroutes_fresh_sessions(dev, pair):
    """Sessions that never reached the cloud (still fresh) survive a
    replica death too: they re-run as fresh sessions on the survivor."""
    n, max_new = 2, 8
    prompts = _prompts(n, seed=13)
    engines = [_mk_engine(pair), _mk_engine(pair)]
    router = ReplicaRouter(build_fleet(dev, engines), policy="round-robin")
    sess = [router.open_session(p, max_new) for p in prompts]
    moved = router.kill_replica(0)              # before any step()
    assert moved == 1                           # session 0 was on replica 0
    while router.step():
        pass
    assert all(s.done and s.metrics is not None for s in sess)
    _assert_pristine(engines[1])
    ref = SY.run_synera(dev, engines[1], prompts, max_new, concurrency=1)
    assert _tokens([s.metrics for s in sess]) == \
        [[int(t) for t in o] for o in ref.outputs]


# ---------------------------------------------------------------------------
# Fault injection: fleet saturation -> degrade to device-only
# ---------------------------------------------------------------------------

def test_saturation_degrades_to_device(dev, fleet4):
    """With every replica past its queue cap the router does not 429:
    the stream completes device-only (SLM solo, zero cloud tokens) and
    ``degraded_streams`` increments."""
    prompts = _prompts(3, seed=17)
    max_new = 8
    router = ReplicaRouter(build_fleet(dev, fleet4[:1]),
                           policy="least-loaded", replica_queue_cap=2)
    s1 = router.open_session(prompts[0], max_new)
    s2 = router.open_session(prompts[1], max_new)
    s3 = router.open_session(prompts[2], max_new)   # fleet saturated
    # the degraded stream completed synchronously, solo on the device
    assert s3.done and s3.metrics is not None
    assert len(s3.metrics.tokens) == max_new
    assert s3.metrics.n_cloud_tokens == 0
    assert s3.metrics.n_cloud_fed_tokens == 0
    assert router.degraded_streams == 1
    assert router.owner[id(s3)] == -1
    while router.step():
        pass
    assert s1.done and s2.done
    stats = router.stats()
    assert stats["degraded_streams"] == 1
    assert stats["completed_streams"] == 3          # degraded one included
    # capacity freed: the next open goes back to the replica
    s4 = router.open_session(prompts[0], max_new)
    assert router.owner[id(s4)] == 0
    while router.step():
        pass
    # same prompt, both cloud-verified: determinism unaffected by the
    # degrade episode in between
    assert _tokens([s4.metrics]) == _tokens([s1.metrics])


# ---------------------------------------------------------------------------
# Prefix-affinity x persistent prefix cache (PR 8) composition
# ---------------------------------------------------------------------------

def test_prefix_affinity_lands_on_cached_replica(dev, pair):
    """Two waves sharing a 32-token system prompt: prefix-affinity
    concentrates every stream on the replica that already holds the
    prefix (wave 2 revives/dedupes retained blocks); least-loaded on a
    cold fleet spreads the same wave and reuses nothing."""
    rng = np.random.default_rng(23)
    common = [int(t) for t in rng.integers(1, 60, 32)]
    wave1 = [common + [int(t) for t in rng.integers(1, 60, 8)]
             for _ in range(2)]
    wave2 = [common + [int(t) for t in rng.integers(1, 60, 8)]
             for _ in range(2)]
    max_new = 8

    engines = [_mk_engine(pair, retain_prefix=True) for _ in range(2)]
    router = ReplicaRouter(build_fleet(dev, engines),
                           policy="prefix-affinity")
    m1 = router.serve(wave1, max_new, concurrency=1)
    owners1 = [router.owner[id(s)] for s in router.sessions]
    fed_w1 = router.stats()["prefill_fed_tokens"]
    reuse_w1 = (router.stats()["revived_blocks"]
                + router.stats()["dedupe_hit_blocks"])
    m2 = router.serve(wave2, max_new, concurrency=2)
    owners2 = [router.owner[id(s)] for s in router.sessions[len(wave1):]]
    stats = router.stats()

    # wave 1 stream 2 and all of wave 2 land where the prefix lives
    assert set(owners1) == {0} and set(owners2) == {0}
    assert stats["affinity_hits"] >= 3          # every probe after the first
    # wave 2 adopted retained blocks instead of re-prefilling the prefix
    reuse_w2 = (stats["revived_blocks"] + stats["dedupe_hit_blocks"])
    assert reuse_w2 > reuse_w1
    assert stats["revived_blocks"] > 0
    # and fed strictly fewer prefill tokens than a cold wave would
    assert (stats["prefill_fed_tokens"] - fed_w1
            < sum(len(p) for p in wave2))

    # identity: same waves on a single engine, sequentially
    assert _tokens(m1) == _oracle(dev, engines[0], wave1, max_new)
    assert _tokens(m2) == _oracle(dev, engines[0], wave2, max_new)

    # control: a COLD least-loaded fleet spreads the wave; nothing to
    # revive, nothing to dedupe across replicas
    cold = [_mk_engine(pair, retain_prefix=True) for _ in range(2)]
    router_ll = ReplicaRouter(build_fleet(dev, cold), policy="least-loaded")
    m2c = router_ll.serve(wave2, max_new, concurrency=1)
    st = router_ll.stats()
    assert st["revived_blocks"] + st["dedupe_hit_blocks"] == 0
    assert st["affinity_hits"] == 0
    owners_ll = [router_ll.owner[id(s)] for s in router_ll.sessions]
    assert set(owners_ll) == {0, 1}             # spread, not concentrated
    assert _tokens(m2c) == _oracle(dev, engines[0], wave2, max_new)
