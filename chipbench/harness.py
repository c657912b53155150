"""One run of one cell: set-up, the measured window, the reduction to
metrics, and the comparison that decides ``correct``.

The system under test is the program's own serving path: ``CloudEngine``
(paged KV pool, fused ``cloud_verify`` step with the paged Pallas
``partial_prefill`` kernel), ``VerificationAwareScheduler`` and
``SyneraServer`` on ``RealClock(pace=False)``, on one chip.  The
benchmark brings the load (``replay.py``), the weights (``weights.py``)
and the reference (``references/``); it wraps the calls into each layer
to count rows and to put host spans around them.
"""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from chipbench import cells, flops, replay

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE.parent / ".chipbench_trace"
DRAIN_S = 60.0           # how long past the window the drain may run
TRACE_S = 4.0            # traced part of the window (--trace 1)


def log(tag: str, **fields):
    """An earlier line of standard output: one JSON object per tag."""
    print(f"chipbench {tag} " + json.dumps(fields, default=float), flush=True)


class CompileLog:
    """Backend compile seconds per jitted program name, and persistent
    cache hits, from JAX's monitoring events (as ``chip_smoke.py``)."""

    def __init__(self):
        import jax

        self.secs: dict[str, list[float]] = {}
        self.names: list[str] = []     # every compile, in order
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs.setdefault(kw.get("fun_name", "?"), []).append(duration)
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def count(self) -> int:
        return sum(map(len, self.secs.values()))

    def summary(self, min_secs: float = 0.5) -> dict:
        out = {k: {"n": len(v), "secs": sum(v)}
               for k, v in sorted(self.secs.items()) if sum(v) >= min_secs}
        out["all_programs_secs"] = sum(map(sum, self.secs.values()))
        out["compiles"] = self.count
        return out


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind``; a device not in the table is an
    error, never a default."""
    table = cells.load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"chipbench: no peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


# ---------------------------------------------------------------------------
# Counting and spans around the calls into the engine
# ---------------------------------------------------------------------------

@dataclass
class CallLog:
    """Rows the engine computed and fed, per kind, and the traced calls'
    operation counts."""
    rows_computed: int = 0
    rows_valid: int = 0
    prefill_fed: int = 0
    prompt_tokens: int = 0       # prompts of prefills completed in window
    prompt_fed: int = 0          # of those, tokens the prefill fed
    iteration_prefill_fed: int = 0
    dispatched: list = field(default_factory=list)  # traced step calls' (kf, kb)
    traced: list = field(default_factory=list)   # (kind, step_flops, [(kf, kb)])


class Counting:
    """Wraps every engine's step programs and its ``feed`` / ``prefill``.
    Rows computed are read off the arrays each step program is given, as
    the engine padded and split them; rows that carry a token are those
    of the call with a position >= 0.  When tracing, it opens a host span
    around each call and counts the kernel's work from the positions each
    step program was given."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.log = CallLog()
        self.counting = False      # inside the measured window
        self.tracing = False       # inside the traced part of it

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)

    def _dispatched(self, positions):
        if self.counting:
            self.log.rows_computed += int(np.prod(positions.shape))
        if self.tracing:
            self.log.dispatched.append(
                flops.kernel_cost(self.cfg, np.asarray(positions)))

    def _account(self, kind: str, positions, n_dispatched: int):
        valid = int((np.asarray(positions) >= 0).sum())
        if kind == "prefill":
            self.log.iteration_prefill_fed += valid
        if self.counting:
            self.log.rows_valid += valid
            if kind == "prefill":
                self.log.prefill_fed += valid
        if self.tracing:
            self.log.traced.append((
                kind, flops.step_flops(self.cfg, positions),
                self.log.dispatched[n_dispatched:]))

    def wrap(self, engine):
        for name in ("_step", "_step_greedy"):
            step = getattr(engine, name)

            def counted_step(params, cache, tokens, positions, *rest,
                             _step=step):
                self._dispatched(positions)
                return _step(params, cache, tokens, positions, *rest)

            setattr(engine, name, counted_step)
        feed, prefill = engine.feed, engine.prefill

        def counted_feed(tokens, positions, targets=None, sel_idx=None,
                         need_dists=True):
            n = len(self.log.dispatched)
            with self.span("bench.verify"):
                out = feed(tokens, positions, targets, sel_idx,
                           need_dists=need_dists)
            self._account("verify", positions, n)
            return out

        def counted_prefill(tokens, positions):
            n = len(self.log.dispatched)
            with self.span("bench.prefill"):
                out = prefill(tokens, positions)
            self._account("prefill", positions, n)
            return out

        engine.feed, engine.prefill = counted_feed, counted_prefill


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

class Run:
    """One run of ``cell`` (``cells.cell`` output, or the same shape with
    a smaller configuration for a test) on ``devices``."""

    LATENCY_PERCENTILES = {"verify": (50, 95), "prefill": (50, 80, 90)}

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 devices, t_process0: float):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = list(devices)
        self.t0 = t_process0
        self.model = cell["model"]
        self.mix = cell["mix"]
        self.sv = self.model["serving"]
        self.cfg = cells.model_config(self.model)
        self.clog = CompileLog()
        self.counting = Counting(self.cfg)
        self.records: list[replay.StreamRecord] = []
        self._awaiting: list[replay.StreamRecord] = []   # prefill pending
        self.checked: list[int] | None = None   # stream ids compared

    # -- set-up ---------------------------------------------------------
    def build(self):
        import jax

        from chipbench import weights
        from repro.serving.engine import CloudEngine
        from repro.serving.link import RealClock
        from repro.serving.server import SyneraServer

        t = time.perf_counter()
        self.params = weights.make_params(self.cfg, self.seed,
                                          self.devices[0])
        jax.block_until_ready(self.params)
        t_weights = time.perf_counter() - t
        kw = dict(max_slots=self.sv["slots"], s_max=self.sv["s_max"],
                  cache_impl="paged", block_size=self.sv["block_size"],
                  pool_blocks=self.sv["pool_blocks"], share_prefix=True,
                  retain_prefix=True)
        if "feed_buckets" in self.sv:
            kw["feed_buckets"] = tuple(self.sv["feed_buckets"])
        if len(self.devices) != 1:
            raise SystemExit("chipbench: a cell serves from one chip")
        self.engine = CloudEngine(self.cfg, self.params,
                                  device=self.devices[0], **kw)
        self.counting.wrap(self.engine)
        self.clock = RealClock(pace=False)
        self.device = replay.ReplayDevice(self.mix, self.cfg.vocab,
                                          lambda: self.clock.now_ms)
        self.server = SyneraServer(self.device, self.engine,
                                   chunk=self.sv["chunk"], clock=self.clock)
        self._watch_prefills()
        t = time.perf_counter()
        self.warm_shapes()
        log("setup_parts", weights_s=t_weights,
            warm_shapes_s=time.perf_counter() - t,
            params=sum(x.size for x in jax.tree.leaves(self.params)),
            compile=self.clog.summary())

    def warm_shapes(self):
        """Compile, through the engine's own ``feed``, ``prefill``,
        ``alloc_prompt`` and ``reset_slot``, every program the traffic
        drives: the greedy step at each feed bucket (padding rows only:
        position -1 writes nothing); for each count of slots whose
        prompts end in one prefill, a prefill of one row per slot, which
        compiles the last-row gather, then the release of those slots,
        which compiles the pool's block invalidation; and two prompts
        whose first tokens agree, the second of which copies that row
        from the first's block (the prefix cache's partial-block tail,
        which unshared prompts meet whenever a first token repeats)."""
        import jax

        e = self.engine
        B = e.max_slots
        for b in e.feed_buckets:
            e.feed(np.zeros((B, b), np.int32),
                   np.full((B, b), -1, np.int32), need_dists=False)
        for n in range(1, B + 1):
            pos = np.full((B, 1), -1, np.int32)
            pos[:n] = 0
            e.prefill(np.ones((B, 1), np.int32), pos)
            for slot in range(n):
                e.reset_slot(slot)
        n = e.allocator.block_size + 1
        for slot, prompt in enumerate(([1] * n, [1] + [2] * (n - 1))):
            shared = e.alloc_prompt(slot, prompt)
            tokens = np.zeros((B, n), np.int32)
            pos = np.full((B, n), -1, np.int32)
            tokens[slot] = prompt
            pos[slot, shared:] = np.arange(shared, n)
            e.prefill(tokens, pos)
        for slot in (0, 1):
            e.reset_slot(slot)
        jax.block_until_ready(e.cache)

    def _watch_prefills(self):
        """Time each prompt prefill's completion on the host: wrap the
        scheduler's iteration and match its ``prefill_done`` events to
        the streams waiting on them."""
        sched = self.server.sched
        it = sched.run_iteration
        waiting = self._awaiting

        def run_iteration():
            log = self.counting.log
            log.iteration_prefill_fed = 0
            with self.counting.span("bench.step"):
                evs = it()
            done = [ev for ev in evs if ev.kind == "prefill_done"]
            if done:
                now = self.clock.now_ms
                by_rid = {r.session.prefill_rid: r for r in waiting}
                prompts = 0
                for ev in done:
                    rec = by_rid.get(ev.req_id)
                    if rec is not None:
                        rec.calls[0].reply_ms = now
                        waiting.remove(rec)
                        prompts += len(rec.spec.prompt)
                if self.counting.counting:
                    log.prompt_tokens += prompts
                    log.prompt_fed += log.iteration_prefill_fed
            return evs

        sched.run_iteration = run_iteration

    # -- traffic ----------------------------------------------------------
    def open_stream(self, spec: replay.StreamSpec, start_ms: float):
        rec = replay.StreamRecord(spec, start_ms)
        self.device.pending = rec
        with self.counting.span("bench.open"):
            s = self.server.open_session(spec.prompt, spec.max_new,
                                         arrival_ms=start_ms)
        rec.session = s
        self._awaiting.append(rec)
        self.records.append(rec)
        return rec

    def _next_due(self, now: float, next_arrival: float) -> float:
        due = [next_arrival]
        for q in (self.server.sched.prefill_q, self.server.sched.verify_q):
            due += [r.arrival_ms for r in q if r.arrival_ms > now]
        return min(due)

    def _window_open(self) -> list:
        """Calls due in the window that have no reply yet."""
        w0, w1 = self.w0, self.w1
        out = []
        for rec in self.records:
            if rec.start_ms >= w1:
                break
            for c in rec.calls:
                if w0 <= c.due_ms < w1 and c.reply_ms is None \
                        and not _withdrawn(rec, c):
                    out.append(c)
        return out

    def pool(self) -> list:
        """The streams the check may take: those whose scheduled arrival
        falls in the window.  ``replay.schedule`` gives every seed the
        same set of window streams, so the pool is a property of the
        schedule, not of how fast the verifier answers."""
        return [r for r in self.records if self.in_window(r.start_ms)]

    def serve(self):
        """Open streams on their schedule, step the front end, and stop
        once every call due in the window has its reply and every pool
        stream is done (or the drain limit passed); arrivals of the
        drain keep coming meanwhile."""
        import jax

        mix = self.mix
        warm_s = float(mix["warm_s"])
        specs = replay.schedule(
            mix, self.seed, [warm_s, warm_s + self.seconds,
                             warm_s + self.seconds + DRAIN_S],
            self.cfg.vocab, self.sv["s_max"])
        t_start = self.clock.now_ms
        arrivals = [t_start + s.arrival_s * 1e3 for s in specs]
        self.w0 = t_start + warm_s * 1e3
        self.w1 = self.w0 + self.seconds * 1e3
        trace_at = self.w0 + max(0.0, (self.seconds - TRACE_S) / 2) * 1e3
        trace_end = trace_at + min(TRACE_S, self.seconds) * 1e3
        state = "warm"
        lateness = []
        i = 0
        ann = None
        while True:
            now = self.clock.now_ms
            while i < len(specs) and arrivals[i] <= now:
                lateness.append(now - arrivals[i])
                self.open_stream(specs[i], arrivals[i])
                i += 1
            if state == "warm" and now >= self.w0:
                state = "window"
                self.setup_s = time.perf_counter() - self.t0
                self.compiles_at_w0 = self.clog.count
                self.counting.counting = True
                self.base = self._counters()
            if state == "window" and now >= self.w1:
                state = "drain"
                self.counting.counting = False
                self.compiles_in_window = self.clog.count - self.compiles_at_w0
                self.end = self._counters()
                self.peak_bytes = max(
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in self.devices)
            if self.trace and ann is None and state == "window" \
                    and now >= trace_at and not hasattr(self, "trace_path"):
                from jax.profiler import ProfileOptions, TraceAnnotation

                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=opts)
                self.counting.tracing = True
                ann = TraceAnnotation("bench.traced")
                ann.__enter__()
            if ann is not None and (now >= trace_end or state != "window"):
                ann.__exit__(None, None, None)
                self.counting.tracing = False
                jax.profiler.stop_trace()
                ann = None
                self.trace_path = sorted(glob.glob(
                    str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True))[-1]
            if state == "drain":
                pending = self._window_open()
                unfinished = [r for r in self.pool() if not r.done]
                if not (pending or unfinished) \
                        or now >= self.w1 + DRAIN_S * 1e3:
                    self.unanswered = len(pending)
                    self.unfinished = len(unfinished)
                    self.drain_s = (now - self.w1) / 1e3
                    break
            n0 = self.server.sched.iterations
            self.server.step()
            if self.server.sched.iterations == n0:
                nxt = arrivals[i] if i < len(arrivals) else now + 5.0
                wait = min(self._next_due(self.clock.now_ms, nxt)
                           - self.clock.now_ms, 2.0)
                if wait > 0.05:
                    with self.counting.span("bench.wait"):
                        time.sleep(wait / 1e3)
        self.lateness_ms = lateness
        for s in list(self.server.sessions):   # free the live streams' slots
            if not s.done:
                self.server.cancel(s)

    def _counters(self) -> dict:
        st = self.server.sched
        return dict(prefill_fed=self.counting.log.prefill_fed,
                    opened=len(self.records), preemptions=st.preemptions,
                    iterations=st.iterations,
                    verify_iterations=st.verify_iterations,
                    prefill_iterations=st.prefill_iterations,
                    bytes_to_host=self.engine.bytes_to_host,
                    queued_prefills=sum(1 for r in st.prefill_q
                                        if r.arrival_ms <= self.clock.now_ms))

    # -- end-to-end metrics ---------------------------------------------------
    def in_window(self, t_ms: float) -> bool:
        return self.w0 <= t_ms < self.w1

    def end_to_end(self) -> dict:
        """Every call due in the window: its latency from its due time to
        its reply reaching the stream, or a failure if none came; and
        the tokens emitted to streams in the window."""
        lat = {"verify": [], "prefill": []}
        attempted = failed = tokens = 0
        for rec in self.records:
            for c in rec.calls:
                if not self.in_window(c.due_ms) or _withdrawn(rec, c):
                    continue
                attempted += 1
                if c.reply_ms is None:
                    failed += 1
                    continue
                lat[c.kind].append(c.reply_ms - c.due_ms)
            tokens += sum(n for t, n in rec.emits if self.in_window(t))
        self.attempted, self.failed = attempted, failed
        self.latencies = lat
        self.samples = {k: len(v) for k, v in lat.items()}
        out = {"setup_s": self.setup_s,
               "stream_tokens_per_s": tokens / self.seconds}
        for kind, qs in self.LATENCY_PERCENTILES.items():
            for q in qs:
                if lat[kind]:
                    out[f"{kind}_p{q}_ms"] = percentile(lat[kind], q)
        return out

    def lifetimes_s(self) -> list:
        """Seconds from arrival to the last token, of every finished
        stream that arrived before the window closed."""
        return [(rec.emits[-1][0] - rec.start_ms) / 1e3 for rec in
                self.records if rec.done and rec.emits
                and rec.start_ms < self.w1]

    # -- per-layer metrics ----------------------------------------------------
    def per_layer(self) -> dict:
        from chipbench import tracing

        peak = peaks_for(self.devices[0].device_kind)
        tr = None
        if getattr(self, "trace_path", None):
            used = {d.id for d in self.devices}
            tr = tracing.reduce(self.trace_path, used)
        if tr is not None:
            from chipbench.tracing import is_kernel

            kern = [o for d in tr.ops.values() for o in d if is_kernel(o.name)]
            log("trace", path=self.trace_path, window_s=tr.window_s,
                busy_s=tr.busy_s, ops=sum(map(len, tr.ops.values())),
                modules=sorted({m.name.split("(")[0]
                                for d in tr.modules.values() for m in d}),
                spans={n: len(tr.spans_named(n)) for n in (
                    "bench.verify", "bench.prefill", "bench.step")},
                kernel_events=len(kern),
                kernel_names=sorted({o.name[:160] + " ... " + o.name[-160:]
                                     for o in kern})[:12],
                kernel_calls_expected=sum(
                    len(subs) for _, _, subs in self.counting.log.traced)
                * self.cfg.n_layers)
        ctx = dict(run=self, trace=tr, peak=peak, cfg=self.cfg,
                   counts=self.counting.log, base=self.base, end=self.end)
        self.trace_data = tr
        out = {}
        for m in self.cell["per_layer"]:
            reader = _load_reader(m["name"])
            v = reader(ctx)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out

    # -- correctness --------------------------------------------------------
    def sample_streams(self) -> list:
        """Finished pool streams that the cloud served, the longest
        first, then in an order drawn from the seed, until they hold the
        mix's ``check_tokens`` cloud-served tokens: ``(tokens, served)``
        with ``served`` the ``(position, token)`` pairs the cloud gave.
        The ids of the streams taken are kept in ``checked``."""
        done = [r for r in self.pool() if r.done and r.served]
        self.checked = []
        if not done:
            return []
        rng = np.random.default_rng([self.seed % (1 << 63), 7])
        longest = max(range(len(done)), key=lambda j: len(done[j].seq))
        pick, served = [], 0
        for j in [longest] + [j for j in rng.permutation(len(done))
                              if j != longest]:
            if served >= self.mix["check_tokens"]:
                break
            pick.append(j)
            served += len(done[j].served)
        self.checked = [done[j].spec.sid for j in sorted(pick)]
        return [(np.asarray(done[j].seq, np.int32), done[j].served)
                for j in sorted(pick)]

    def free_program(self):
        """Drop the program's state (pools, weight copies) before the
        reference runs; the benchmark's own weights stay."""
        if self.engine is not None:
            self.engine.cache = self.engine.params = None
        self.engine = self.server = None

    def check(self, streams=None) -> dict:
        """Compare every token the cloud served the sampled streams (or
        ``streams``) with the plain reference: the widest gap by which a
        served token's logit lies below the reference's best, over at
        least the mix's ``check_tokens`` served tokens; and every pool
        stream finished."""
        if streams is None:
            streams = self.sample_streams()
        pool = self.pool()
        self.free_program()
        ref = _load_reference(self.model["reference"])
        t = time.perf_counter()
        gaps = ref.served_gaps(self.model, self.params, streams,
                               devices=self.devices) if streams else []
        in_vocab = all(0 <= tok < self.cfg.vocab
                       for _, s in streams for _, tok in s)
        checks = {
            "max_logit_gap": {"value": max(gaps) if gaps else None,
                              "limit": self.model["check"]["max_logit_gap"]},
            "unanswered_calls": {"value": self.unanswered, "limit": 0},
            "unfinished_streams": {"value": self.unfinished, "limit": 0},
            "served_tokens": {"value": len(gaps),
                              "min": self.mix["check_tokens"]},
            "tokens_out_of_vocab": {"value": int(not in_vocab), "limit": 0},
        }
        self.correct = all(
            c["value"] is not None and (c["value"] <= c["limit"]
                                        if "limit" in c else
                                        c["value"] >= c["min"])
            for c in checks.values())
        log("check", streams=len(streams), checked=self.checked,
            pool_streams=len(pool),
            pool_served_tokens=sum(len(r.served) for r in pool),
            reference_s=time.perf_counter() - t,
            gap_p50=percentile(gaps, 50) if gaps else None,
            correct=self.correct,
            **{k: v["value"] for k, v in checks.items()})
        return checks


def _withdrawn(rec: replay.StreamRecord, c: replay.Call) -> bool:
    """A prompt prefill that the server cancelled because its stream
    kept every chunk on the device and finished before the prefill got
    a slot: the stream never waited on it."""
    return (c.kind == "prefill" and c.reply_ms is None and rec.done
            and len(rec.calls) == 1)


def _load_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_reader(name: str):
    return _load_file(HERE / "metrics" / f"{name}.py",
                      f"chipbench_metric_{name.replace('.', '_')}").read


def _load_reference(name: str):
    return _load_file(HERE / "references" / f"{name}.py",
                      f"chipbench_reference_{name}")


def device_info(devices) -> dict:
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def execute(cell: dict, seed: int, seconds: float, trace: bool, devices,
            t_process0: float) -> dict:
    """A whole run; returns the result object (the last line)."""
    run = Run(cell, seed, seconds, trace, devices, t_process0)
    run.build()
    run.serve()
    e2e = run.end_to_end()
    life = run.lifetimes_s() or [float("nan")]
    log("serve", attempted=run.attempted, failed=run.failed,
        samples=run.samples, streams_opened=len(run.records),
        drain_s=run.drain_s,
        lateness_p50_ms=percentile(run.lateness_ms, 50),
        lateness_max_ms=max(run.lateness_ms),
        compiles_in_window=run.compiles_in_window,
        compiled_in_window=run.clog.names[run.compiles_at_w0:][
            :run.compiles_in_window],
        lifetime_p50_s=percentile(life, 50),
        lifetime_p95_s=percentile(life, 95), lifetime_max_s=max(life),
        counters={k: run.end[k] - run.base[k] for k in run.end},
        rows_computed=run.counting.log.rows_computed,
        rows_valid=run.counting.log.rows_valid, **e2e)
    dev = device_info(devices)
    dev["memory_peak_bytes"] = int(run.peak_bytes)
    result = {"correct": False, "attempted": run.attempted,
              "failed": run.failed}
    if trace:
        metrics = run.per_layer()
        tr = run.trace_data
        dev["busy_s"] = tr.busy_s if tr else 0.0
        dev["window_s"] = tr.window_s if tr else 0.0
        breakdown = tr.breakdown() if tr else None
    else:
        want = {m["name"]: m for m in cell["end_to_end"]}
        metrics = {k: {"value": float(v), "unit": want[k]["unit"]}
                   for k, v in e2e.items() if k in want}
        breakdown = None
    checks = run.check()
    result.update(correct=run.correct, metrics=metrics, device=dev)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        bound = "limit" if "limit" in c else "min"
        print(f"check {name} {c['value']} {bound} {c[bound]}",
              file=sys.stderr, flush=True)
    return result
