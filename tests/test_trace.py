"""Unified tracing layer tests (serving/trace.py).

Two families of invariants:

* **Attribution** — every completed stream's exclusive stall buckets
  sum exactly to its wall time (``bucket_sum == t_ms``), under plain
  serving and under forced preemption + host swap + mid-run replica
  death; ``Tracer.window_parts`` decomposes synthetic charge streams
  into the documented categories.

* **Passivity** — tracing must never change behavior: token streams
  are byte-identical with tracing on or off (property-tested over a
  shared-prefix + swap paged fleet), and the exported Chrome
  trace-event JSON passes the structural checker shipped in
  ``tools/check_trace.py``.

* **Host regions and queue records** — regions nest as opened, every
  engine region lies inside an event-loop step, they leave the charge
  spans (and so the attribution) alone, and every fed request has one
  queue record stamped with the iteration that first fed it.
"""
import http.client
import json
import sys
from pathlib import Path

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
from repro.serving.gateway import protocol as P
from repro.serving.link import RealClock, SimClock
from repro.serving.router import ReplicaRouter
from repro.serving.scheduler import VerificationAwareScheduler
from repro.serving.server import (WAIT_CLOUD, SyneraServer,
                                  aggregate_server_stats, build_fleet)
from repro.serving.trace import (HOST_TID, NULL_TRACER, StreamTimeline,
                                 Tracer, hist_add, hist_from, hist_merge,
                                 hist_new)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
import check_trace  # noqa: E402  (tools/check_trace.py)


# ---------------------------------------------------------------------------
# Unit: StreamTimeline buckets
# ---------------------------------------------------------------------------

def _assert_sums(tl: StreamTimeline):
    assert abs(tl.bucket_sum - tl.t_ms) <= 1e-9 * max(1.0, tl.t_ms)


def test_advance_kinds_map_to_buckets():
    tl = StreamTimeline()
    tl.advance(3.0, "compute")
    tl.advance(2.0, "comm")
    tl.advance(5.0, "stall")    # untraced stall -> other
    assert tl.compute_ms == 3.0 and tl.link_ms == 2.0
    assert tl.other_ms == 5.0 and tl.stall_ms == 5.0
    assert tl.t_ms == 10.0
    _assert_sums(tl)


def test_advance_stall_overlap_masks_front():
    # round trip: uplink 4 | cloud 8 | downlink 3 = 15; PI overlap 5
    # masks the front (all of uplink + 1ms of cloud); stall = 10 tail
    tl = StreamTimeline()
    tl.advance_stall(10.0, 4.0, [("cloud", 8.0)], 3.0, 5.0)
    assert tl.cloud_ms == pytest.approx(7.0)
    assert tl.link_ms == pytest.approx(3.0)
    assert tl.other_ms == pytest.approx(0.0)
    assert tl.t_ms == 10.0 and tl.stall_ms == 10.0
    _assert_sums(tl)


def test_advance_stall_without_parts_lands_in_other():
    tl = StreamTimeline()
    tl.advance_stall(7.5, 4.0, None, 3.0, 0.0)
    assert tl.other_ms == 7.5
    _assert_sums(tl)


def test_advance_stall_mixed_window_parts():
    # window contributed queue + other-stream wait + our cloud time
    tl = StreamTimeline()
    parts = [("queue", 2.0), ("wait", 3.0), ("cloud", 4.0)]
    tl.advance_stall(10.0, 0.5, parts, 0.5, 0.0)
    assert tl.link_ms == pytest.approx(1.0)
    assert tl.queue_ms == pytest.approx(2.0)
    assert tl.batch_wait_ms == pytest.approx(3.0)
    assert tl.cloud_ms == pytest.approx(4.0)
    _assert_sums(tl)


def test_advance_stall_caps_at_stall_total():
    # parts longer than the stall: buckets gain exactly stall_ms
    tl = StreamTimeline()
    tl.advance_stall(5.0, 0.0, [("cloud", 100.0)], 0.0, 0.0)
    assert tl.cloud_ms == pytest.approx(5.0)
    _assert_sums(tl)


# ---------------------------------------------------------------------------
# Unit: histogram helpers + Prometheus exposition
# ---------------------------------------------------------------------------

def test_hist_cumulative_semantics():
    h = hist_from([7.0, 30.0, 99999.0])
    # 7 <= 10, 25-bucket counts 7; all finite buckets cumulative
    le = h["le"]
    assert h["buckets"][le.index(5.0)] == 0
    assert h["buckets"][le.index(10.0)] == 1
    assert h["buckets"][le.index(50.0)] == 2
    assert h["buckets"][-1] == 3 == h["count"]   # +Inf
    assert h["sum"] == pytest.approx(7.0 + 30.0 + 99999.0)
    m = hist_merge([h, hist_from([8.0])])
    assert m["count"] == 4
    assert m["buckets"][le.index(10.0)] == 2


def test_metrics_text_renders_histograms():
    stats = {"completed_streams": 3, "trace": True,
             "hist_ttft_ms": hist_from([7.0, 600.0])}
    text = P.metrics_text(stats)
    assert "synera_completed_streams 3" in text
    assert "synera_trace 1" in text
    assert "# TYPE synera_ttft_ms histogram" in text
    assert 'synera_ttft_ms_bucket{le="10"} 1' in text
    assert 'synera_ttft_ms_bucket{le="1000"} 2' in text
    assert 'synera_ttft_ms_bucket{le="+Inf"} 2' in text
    assert "synera_ttft_ms_count 2" in text
    assert "synera_ttft_ms_sum 607.0" in text


# ---------------------------------------------------------------------------
# Unit: window decomposition
# ---------------------------------------------------------------------------

def test_window_parts_categories():
    tr = Tracer(SimClock())
    tr.span(0.0, 10.0, "prefill", rids=(1,))          # our prompt prefill
    tr.span(10.0, 20.0, "verify", rids=(2,))          # our verify (rewound)
    tr.span(20.0, 30.0, "verify", rids=(3,))          # another stream
    tr.span(30.0, 35.0, "swap_out", slot=0)           # our slot swapped
    tr.instant("rewind", t=30.0, rids=(2,))           # we got preempted
    tr.span(35.0, 45.0, "verify", rids=(2,))          # re-served after
    parts = tr.window_parts(0.0, 45.0, slot=0, vrid=2, prefill_rid=1)
    # serving spans that ended before the rewind were thrown away
    assert parts == [("preempted", 20.0), ("wait", 10.0),
                     ("swap", 5.0), ("cloud", 10.0)]
    assert sum(d for _, d in parts) == pytest.approx(45.0)


def test_window_parts_queue_before_own_prefill():
    tr = Tracer(SimClock())
    tr.span(0.0, 10.0, "verify", rids=(9,))     # other stream ahead of us
    tr.span(10.0, 20.0, "prefill", rids=(1,))   # our prompt prefill
    tr.span(20.0, 30.0, "verify", rids=(2,))
    parts = tr.window_parts(0.0, 30.0, vrid=2, prefill_rid=1)
    assert parts == [("queue", 10.0), ("cloud", 20.0)]


def test_window_parts_uncovered_residual_is_other():
    tr = Tracer(SimClock())
    tr.span(0.0, 4.0, "verify", rids=(2,))
    parts = tr.window_parts(0.0, 10.0, vrid=2)
    assert parts == [("cloud", 4.0), ("other", 6.0)]


def test_window_parts_respects_replica_tag():
    tr = Tracer(SimClock())
    tr.span(0.0, 10.0, "verify", replica=1, rids=(2,))
    # same rid on another replica is someone else's request
    parts = tr.window_parts(0.0, 10.0, replica=0, vrid=2)
    assert parts == [("wait", 10.0)]


def test_null_tracer_is_inert():
    assert not NULL_TRACER and not NULL_TRACER.enabled
    assert NULL_TRACER.stream_begin("s", 0.0) == -1
    assert NULL_TRACER.window_parts(0.0, 1.0) is None
    NULL_TRACER.span(0, 1, "x")
    NULL_TRACER.instant("x")
    with pytest.raises(RuntimeError):
        NULL_TRACER.export("/tmp/never.json")


def test_null_tracer_region_is_one_shared_no_op():
    region = NULL_TRACER.region("synera.step")
    assert NULL_TRACER.region("synera.engine.feed", 3) is region
    with region as r:
        assert r is region
    assert NULL_TRACER.queued(1) is None
    NULL_TRACER.queue(1, "verify", 0.0, 1.0)
    assert not hasattr(NULL_TRACER, "_regions")
    assert NULL_TRACER.to_events() == []


def test_regions_nest_as_opened_and_export_on_the_host_track():
    clock = SimClock()
    tr = Tracer(clock)
    with tr.region("synera.step", 2) as step:
        clock.advance(1.0)
        with tr.region("synera.sched.plan"):
            clock.advance(2.0)
        with tr.region("synera.engine.feed"):
            with tr.region("synera.engine.dispatch"):
                clock.advance(3.0)
        step.args.update(kind="verify", rids=(4, 5))
    with tr.region("synera.step"):
        pass
    regs = tr.regions()
    assert [(n, t0, t1, parent, rep) for n, t0, t1, parent, rep, _ in regs] \
        == [("synera.step", 0.0, 6.0, -1, 2),
            ("synera.sched.plan", 1.0, 3.0, 0, 2),
            ("synera.engine.feed", 3.0, 6.0, 0, 2),
            ("synera.engine.dispatch", 3.0, 6.0, 2, 2),
            ("synera.step", 6.0, 6.0, -1, 0)]
    assert regs[0][5] == {"kind": "verify", "rids": (4, 5)}
    host = [e for e in tr.to_events() if e.get("tid") == HOST_TID]
    assert {(e["ph"], e["pid"]) for e in host} == {("X", 3), ("X", 1),
                                                   ("M", 3), ("M", 1)}
    names = [e["args"]["name"] for e in host if e["ph"] == "M"]
    assert names == ["host", "host"]
    step_ev = [e for e in host if e["name"] == "synera.step"][0]
    assert step_ev["dur"] == 6000.0 and step_ev["args"]["rids"] == (4, 5)
    errors, _ = check_trace.check_events(tr.to_events())
    assert errors == [], errors


def test_regions_and_queue_records_leave_window_parts_unchanged():
    def charged(with_regions):
        clock = SimClock()
        tr = Tracer(clock)
        for t0, t1, kind, rids in [(0.0, 10.0, "prefill", (1,)),
                                   (10.0, 20.0, "verify", (3,)),
                                   (20.0, 30.0, "verify", (2,))]:
            if with_regions:
                clock.advance_to(t0)
                with tr.region("synera.step"):
                    clock.advance_to(t1)
                tr.queue(rids[0], kind, t0 - 1.0, t0)
            tr.span(t0, t1, kind, rids=rids)
        return tr.window_parts(0.0, 35.0, vrid=2, prefill_rid=1)

    assert charged(True) == charged(False) == [
        ("cloud", 10.0), ("wait", 10.0), ("cloud", 10.0), ("other", 5.0)]


def test_queue_keeps_the_first_dispatch_per_replica():
    tr = Tracer(SimClock())
    tr.queue(7, "verify", 1.0, 4.0)
    tr.queue(7, "verify", 1.0, 9.0)            # a refeed after a rewind
    tr.queue(7, "verify", 2.0, 3.0, replica=1)
    assert tr.queued(7) == ("verify", 1.0, 4.0)
    assert tr.queued(7, 1) == ("verify", 2.0, 3.0)
    assert sorted(tr.queue_records()) == [(0, 7, "verify", 1.0, 4.0),
                                          (1, 7, "verify", 2.0, 3.0)]


# ---------------------------------------------------------------------------
# Integration: serving runs
# ---------------------------------------------------------------------------

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


def test_traced_run_buckets_sum_and_byte_identity(dev, pair, tmp_path):
    prompts = _prompts(4, shared=4, seed=3)
    base = SY.run_synera(dev, _mk_engine(pair), prompts, 8, concurrency=4)
    res = SY.run_synera(dev, _mk_engine(pair), prompts, 8, concurrency=4,
                        trace=True)
    assert res.outputs == base.outputs            # tracing is passive
    for m in res.metrics:
        _assert_sums(m.timeline)
    sched = res.extras["scheduler"]
    assert sched["trace"] is True
    assert sched["stall_wall_ms"] == pytest.approx(
        sum(m.timeline.t_ms for m in res.metrics))
    assert sched["stall_wall_ms"] == pytest.approx(
        sched["stall_device_ms"] + sched["stall_cloud_ms"]
        + sched["stall_link_ms"] + sched["stall_queue_ms"]
        + sched["stall_batch_wait_ms"] + sched["stall_swap_ms"]
        + sched["stall_preempted_ms"] + sched["stall_other_ms"])
    assert sched["hist_e2e_ms"]["count"] == len(prompts)
    # exported trace passes the structural + bucket-sum checker
    out = tmp_path / "trace.json"
    res.extras["tracer"].export(str(out))
    errors, summary = check_trace.check_file(str(out),
                                             min_streams=len(prompts))
    assert errors == [], errors
    assert summary["buckets_checked"] == len(prompts)


def test_untraced_stats_carry_no_stall_attribution(dev, pair):
    res = SY.run_synera(dev, _mk_engine(pair), _prompts(2), 4,
                        concurrency=2)
    sched = res.extras["scheduler"]
    assert sched["trace"] is False
    # with tracing off the stall portion is unattributed by design
    assert sched["stall_cloud_ms"] == 0.0
    assert sched["stall_queue_ms"] == 0.0


def test_fleet_pressure_buckets_sum(dev, pair):
    """Preemption + host swap + mid-run replica kill: every surviving
    stream's buckets still sum to its wall time, and the pressure
    actually shows up in the swap/preempted/queue buckets."""
    n, max_new = 6, 12
    prompts = _prompts(n, length=12, seed=11)
    engines = [_mk_engine(pair, block_size=4, pool_blocks=24, swap=True)
               for _ in range(2)]
    clock = SimClock()
    tracer = Tracer(clock)
    router = ReplicaRouter(
        build_fleet(dev, engines, clock=clock, tracer=tracer),
        policy="round-robin")
    sess = [router.open_session(p, max_new) for p in prompts]
    for _ in range(400):
        router.step()
        if any(s.state == WAIT_CLOUD
               for s in router.replicas[0].sessions if not s.done):
            break
    else:
        pytest.fail("replica 0 never reached a mid-verify state")
    router.kill_replica(0)
    while router.step():
        pass
    assert all(s.done for s in sess)
    for s in sess:
        _assert_sums(s.metrics.timeline)
    stats = router.stats()
    assert stats["dead_replicas"] == 1
    assert stats["completed_streams"] == n
    assert stats["stall_wall_ms"] == pytest.approx(
        sum(s.metrics.timeline.t_ms for s in sess))
    pressured = (stats["stall_swap_ms"] + stats["stall_preempted_ms"]
                 + stats["stall_queue_ms"] + stats["stall_batch_wait_ms"])
    assert pressured > 0.0
    # the trace records the fleet events end-to-end
    kinds = {k for _, k, *_ in tracer._instants}
    assert "replica_kill" in kinds
    # rerouted streams carry a per-stream "reroute" marker
    snames = {nm for rec in tracer._streams.values()
              for nm, _, _ in rec.instants}
    assert "reroute" in snames


def test_degraded_streams_fold_into_fleet_stats(dev, pair):
    """Device-only degraded sessions belong to no replica; their
    buckets and latency samples still land in the aggregate view."""
    prompts = _prompts(3, seed=17)
    res = SY.run_synera_fleet(dev, [_mk_engine(pair)], prompts, 6,
                              policy="round-robin", replica_queue_cap=1,
                              concurrency=3, trace=True)
    sched = res.extras["scheduler"]
    assert sched["degraded_streams"] >= 1
    assert sched["completed_streams"] == len(prompts)
    assert sched["hist_e2e_ms"]["count"] == len(prompts)
    assert sched["stall_wall_ms"] == pytest.approx(
        sum(m.timeline.t_ms for m in res.metrics))
    for m in res.metrics:
        _assert_sums(m.timeline)


def _http(port, method, path, obj=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
    try:
        body = json.dumps(obj) if obj is not None else None
        conn.request(method, path, body,
                     {"Content-Type": "application/json"} if body else {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_gateway_traces_endpoint_and_metrics_histograms(dev, pair):
    """/v1/traces snapshots a Perfetto-loadable trace mid-flight and
    /metrics exposes the tracer-fed latency histograms."""
    from repro.serving.gateway import Gateway, GatewayConfig
    from repro.serving.link import RealClock
    from repro.serving.server import SyneraServer
    eng = _mk_engine(pair)
    clock = RealClock()
    server = SyneraServer(dev, eng, clock=clock, clamp_arrivals=True,
                          tracer=Tracer(clock))
    gw = Gateway(server, GatewayConfig(port=0, max_new_default=4)).start()
    try:
        status, body = _http(gw.port, "POST", "/v1/chat/completions",
                             {"messages": [{"role": "user",
                                            "content": "3 17 42 9"}],
                              "max_tokens": 4})
        assert status == 200, body
        status, body = _http(gw.port, "GET", "/v1/traces")
        assert status == 200
        doc = json.loads(body)
        assert doc["enabled"] is True
        errors, summary = check_trace.check_events(doc["traceEvents"])
        assert errors == [], errors
        assert summary["streams"] >= 1
        assert summary["buckets_checked"] >= 1
        status, body = _http(gw.port, "GET", "/metrics")
        assert status == 200
        text = body.decode()
        assert "# TYPE synera_ttft_ms histogram" in text
        assert 'synera_e2e_ms_bucket{le="+Inf"}' in text
        assert "synera_stall_wall_ms" in text
    finally:
        gw.close()
    # a gateway without --trace reports the endpoint as disabled
    server2 = SyneraServer(dev, eng, clock=RealClock(),
                           clamp_arrivals=True)
    gw2 = Gateway(server2, GatewayConfig(port=0)).start()
    try:
        status, body = _http(gw2.port, "GET", "/v1/traces")
        assert status == 200
        assert json.loads(body)["enabled"] is False
    finally:
        gw2.close()


def _wall_run(dev, pair, prompts, max_new, tracer=None):
    clock = RealClock() if tracer is None else tracer.clock
    server = SyneraServer(dev, _mk_engine(pair), clock=clock,
                          clamp_arrivals=True, tracer=tracer)
    return server, _tokens(server.serve(prompts, max_new, concurrency=2))


def test_wall_clock_regions_nest_inside_steps(dev, pair):
    """On the wall clock the tracer's regions nest as the program opened
    them: every engine and scheduler region lies inside an event-loop
    step, each step that fed requests names them, and the run's tokens
    are those of the untraced run."""
    prompts = _prompts(3, length=10, seed=21)
    _, base = _wall_run(dev, pair, prompts, 6)
    tracer = Tracer(RealClock())
    server, toks = _wall_run(dev, pair, prompts, 6, tracer)
    assert toks == base
    regs = tracer.regions()
    assert all(r[2] is not None for r in regs)   # every region closed
    names = {r[0] for r in regs}
    assert {"synera.step", "synera.server.open", "synera.sched.plan",
            "synera.engine.feed", "synera.engine.prefill",
            "synera.engine.blocks", "synera.engine.dispatch",
            "synera.engine.sync", "synera.sched.accept",
            "synera.server.deliver"} <= names
    for name, t0, t1, parent, _, _ in regs:
        assert t0 <= t1
        if parent >= 0:
            p = regs[parent]
            assert p[1] <= t0 and t1 <= p[2]
        if name.startswith(("synera.engine.", "synera.sched.")):
            up = parent
            while up >= 0 and regs[up][0] != "synera.step":
                up = regs[up][3]
            assert up >= 0, name
    fed = [r for r in regs if r[0] == "synera.step" and r[5]]
    kinds = {r[5]["kind"] for r in fed}
    assert kinds == {"prefill", "verify"}
    spans = [s for s in tracer._spans if s[2] in ("prefill", "verify")]
    assert sorted(tuple(r[5]["rids"]) for r in fed) == \
        sorted(tuple(s[4]) for s in spans)


def test_every_fed_request_has_one_queue_record(dev, pair):
    """Each request a charge span fed has exactly one queue record: its
    arrival, and the start of the first iteration that fed it."""
    tracer = Tracer(RealClock())
    server, _ = _wall_run(dev, pair, _prompts(3, length=10, seed=22), 6,
                          tracer)
    first = {}
    for t0, _, kind, _, rids, *_ in tracer._spans:
        for rid in rids:
            first.setdefault(rid, (kind, t0))
    recs = tracer.queue_records()
    assert len(recs) == len(first) > 0
    for rep, rid, kind, arrival, dispatch in recs:
        assert rep == 0 and (kind, dispatch) == first[rid]
        assert dispatch >= arrival
    kinds = [k for _, _, k, _, _ in recs]
    assert kinds.count("prefill") == 3 and "verify" in kinds
    queued = [c for rec in tracer._streams.values()
              for c in rec.children if c[0] == "queued"]
    assert len(queued) == len(recs)


def test_engine_counters_and_specialize_instants(pair):
    """The engine counts rows each sub-chunk dispatched (padded to its
    bucket) and fed, block-table uploads and host syncs, and marks the
    first use of each (step, bucket) on the trace."""
    eng = _mk_engine(pair, feed_buckets=(8, 16))
    tracer = Tracer(SimClock())
    eng.set_tracer(tracer)
    B = eng.max_slots
    toks = np.ones((B, 20), np.int32)
    pos = np.full((B, 20), -1, np.int32)
    pos[0] = np.arange(20)                     # 16 + a 4-wide tail -> 8
    eng.alloc_prompt(0, [1] * 20)
    eng.prefill(toks, pos)
    assert eng.rows_computed == {"verify": 0, "prefill": B * (16 + 8)}
    assert eng.rows_fed == {"verify": 0, "prefill": 20}
    assert eng.host_syncs == 1                 # the last row's sub-chunk
    uploads = eng.table_uploads
    assert uploads >= 1
    assert eng.table_upload_bytes == uploads * eng.allocator.table.nbytes
    tok = np.zeros((B, 3), np.int32)
    vpos = np.full((B, 3), -1, np.int32)
    vpos[0] = [20, 21, 22]
    sel = np.full((B, eng.verify_rows_max), -1, np.int32)
    sel[0, :3] = [0, 1, 2]
    eng.feed(tok, vpos, sel_idx=sel, need_dists=False)
    eng.feed(tok, vpos, sel_idx=sel)
    eng.feed(tok, vpos, sel_idx=sel)
    assert eng.rows_computed["verify"] == 3 * B * 8
    assert eng.rows_fed["verify"] == 9 and eng.host_syncs == 4
    # prefill took the greedy step at 16 and 8, the greedy feed nothing
    # new, the first feed with dists the full step at 8
    spec = [n for _, k, _, _, _, n in tracer._instants if k == "specialize"]
    assert spec == [16, 8, 8]
    names = [r[0] for r in tracer.regions()]
    assert names.count("synera.engine.prefill") == 1
    assert names.count("synera.engine.feed") == 3
    assert names.count("synera.engine.sync") == 4


def test_step_without_an_iteration_names_no_batch(dev, pair):
    """A step that runs no scheduler iteration (every session done)
    carries no kind or request ids, even right after a step that fed."""
    tracer = Tracer(RealClock())
    server, _ = _wall_run(dev, pair, _prompts(2, length=10, seed=23), 4,
                          tracer)
    fed = [r for r in tracer.regions() if r[0] == "synera.step" and r[5]]
    assert fed
    n = len(tracer.regions())
    assert server.step() is False
    last = tracer.regions()[n:]
    assert [r[0] for r in last] == ["synera.step", "synera.server.open"]
    assert last[0][5] == {}


def test_server_installs_its_tracer_on_the_engine(dev, pair):
    """The server, not the scheduler, gives its engine (and the
    engine's block pool and swap tier) the tracer; a later server over
    the same engine takes it over."""
    eng = _mk_engine(pair, swap=True)
    parts = (eng, eng.allocator, eng.swap_manager)
    clock = SimClock()
    tracer = Tracer(clock)
    VerificationAwareScheduler(eng, clock=clock, tracer=tracer, replica=1)
    assert all(p.tracer is NULL_TRACER for p in parts)
    SyneraServer(dev, eng, clock=clock, tracer=tracer, replica=1)
    assert all(p.tracer is tracer and p.trace_replica == 1 for p in parts)
    SyneraServer(dev, eng, clock=clock)
    assert all(p.tracer is NULL_TRACER and p.trace_replica == 0
               for p in parts)


def test_server_stats_expose_the_host_counters(dev, pair):
    """The engine's and scheduler's boundary counters reach
    ServerStats, and so the gateway's /metrics, summed over a fleet."""
    prompts = _prompts(3, length=10, seed=24)
    clock = SimClock()
    fleet = build_fleet(dev, [_mk_engine(pair), _mk_engine(pair)],
                        clock=clock)
    ReplicaRouter(fleet, policy="round-robin").serve(prompts, 4)
    per = [srv.server_stats() for srv in fleet]
    for srv, st in zip(fleet, per):
        eng = srv.engine
        assert st.rows_computed == sum(eng.rows_computed.values()) > 0
        assert st.rows_fed == sum(eng.rows_fed.values()) > 0
        assert st.rows_fed < st.rows_computed
        assert (st.table_uploads, st.table_upload_bytes, st.host_syncs) \
            == (eng.table_uploads, eng.table_upload_bytes, eng.host_syncs)
        assert st.table_uploads > 0 and st.host_syncs > 0
        assert st.prompt_tokens_done == srv.sched.prompt_tokens_done
    total = aggregate_server_stats(per)
    for k in ("rows_computed", "rows_fed", "table_uploads",
              "table_upload_bytes", "host_syncs", "prompt_tokens_done"):
        assert getattr(total, k) == sum(getattr(st, k) for st in per)
    assert total.prompt_tokens_done == sum(len(p) for p in prompts)
    text = P.metrics_text(total.as_dict())
    assert f"synera_table_uploads {total.table_uploads}\n" in text
    assert f"synera_host_syncs {total.host_syncs}\n" in text


def test_scheduler_counts_completed_prompt_tokens(dev, pair):
    prompts = _prompts(3, shared=3)
    server = SyneraServer(dev, _mk_engine(pair), clock=SimClock())
    server.serve(prompts, 4, concurrency=3)
    st = server.sched
    assert st.prompt_tokens_done == sum(len(p) for p in prompts)
    # a stream admitted while another holds the shared 16-token lead
    # adopts its block instead of feeding it
    served = st.prompt_tokens_done - st.prefill_fed_tokens
    assert served > 0 and served % 16 == 0


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=2, max_value=10),
       st.integers(min_value=1, max_value=4))
def test_tracing_byte_identity_property(dev, pair, n, max_new, conc):
    """Tracing on/off never changes token streams, across stream
    counts / lengths / concurrency on a shared-prefix + swap fleet."""
    prompts = _prompts(n, shared=n, seed=100 + n + max_new)
    kw = dict(block_size=4, pool_blocks=32, swap=True)
    base = SY.run_synera_fleet(
        dev, [_mk_engine(pair, **kw) for _ in range(2)], prompts, max_new,
        policy="prefix-affinity", concurrency=conc)
    traced = SY.run_synera_fleet(
        dev, [_mk_engine(pair, **kw) for _ in range(2)], prompts, max_new,
        policy="prefix-affinity", concurrency=conc, trace=True)
    assert traced.outputs == base.outputs
    for m in traced.metrics:
        _assert_sums(m.timeline)
    # the traced run also recorded host regions and queue records
    tracer = traced.extras["tracer"]
    assert any(r[0] == "synera.engine.feed" for r in tracer.regions())
    assert {k for _, _, k, _, _ in tracer.queue_records()} == {
        "prefill", "verify"}
