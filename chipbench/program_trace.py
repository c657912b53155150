"""Reductions of the serving program's own record of a run: its host
regions (``synera.*``, ``repro.serving.trace.Tracer.region``) on the
device trace's clock, and its per-request queue records.

Pure functions of a reduced trace (``tracing.TraceData``), the regions
read from its file, and a run's tracer.  The readers
``chipbench/metrics/verify_queue_ms.py`` and ``step_host_gap_ms.py``
use them; ``chipbench/traced_run.py`` runs a cell with the program's
tracer attached and prints them.  They belong in ``tracing.py`` once the
harness attaches the tracer itself (PERF.md, Open questions).
"""
from __future__ import annotations

from bisect import bisect_right

import numpy as np

from chipbench import tracing

PREFIX = "synera."
STEP = "synera.step"
FEEDS = ("synera.engine.feed", "synera.engine.prefill")
OUTSIDE = "outside program spans"


def program_spans(path: str) -> list:
    """The program's host regions in the trace at ``path``, as
    ``tracing.Span`` on the trace's clock, in start order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(tracing.Span(
                        e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9))
    return sorted(out, key=lambda s: (s.t0, -s.t1))


def spans_of(run) -> list:
    """``program_spans`` of a run's trace (read once), or [] untraced."""
    if not hasattr(run, "_program_spans"):
        path = getattr(run, "trace_path", None)
        run._program_spans = program_spans(path) if path else []
    return run._program_spans


def _innermost(spans: list, t0s: list, t: float):
    """The latest-opened span still open at ``t``: regions nest, so it
    is the innermost (``spans`` in start order, ``t0s`` their starts)."""
    for i in range(bisect_right(t0s, t) - 1, -1, -1):
        if spans[i].t1 > t:
            return spans[i]
    return None


def _idle_segments(tr, spans: list, device: int):
    """``(seconds, innermost span or None, t)`` for every piece of the
    device's idle time in the window, cut at every span edge."""
    t0s = [s.t0 for s in spans]
    w0, w1 = tr.window
    busy = tr.busy_intervals(device)
    cuts = {w0, w1}
    cuts.update(busy.ravel().tolist())
    for s in spans:
        cuts.update(t for t in (s.t0, s.t1) if w0 < t < w1)
    cuts = np.array(sorted(cuts))
    starts = busy[:, 0] if len(busy) else np.zeros(0)
    for a, b in zip(cuts[:-1], cuts[1:]):
        m = (a + b) / 2
        k = np.searchsorted(starts, m, side="right") - 1
        if k >= 0 and busy[k, 1] > m:
            continue                       # an op ran here
        yield b - a, _innermost(spans, t0s, m), m


def idle_by_program_span(tr, spans: list) -> dict:
    """Seconds of device idle time in the traced window, by the
    innermost program region open (averaged over the chips used), the
    rest under ``OUTSIDE``; largest first.  ``spans`` in start order,
    as ``program_spans`` gives them."""
    devs = sorted(tr.ops) or [0]
    tot: dict[str, float] = {}
    for d in devs:
        for dt, s, _ in _idle_segments(tr, spans, d):
            key = s.name if s is not None else OUTSIDE
            tot[key] = tot.get(key, 0.0) + dt / len(devs)
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def iteration_steps(tr, spans: list) -> list:
    """``synera.step`` regions inside the window that ran an engine feed
    or prefill."""
    w0, w1 = tr.window
    steps = [s for s in spans if s.name == STEP and s.t0 >= w0
             and s.t1 <= w1]
    feeds = [s for s in spans if s.name in FEEDS]
    return [s for s in steps
            if any(s.t0 <= f.t0 and f.t1 <= s.t1 for f in feeds)]


def step_host_gap_ms(tr, spans: list):
    """Device idle time inside the steps that ran an iteration, per such
    step, in ms (averaged over the chips used); None without one."""
    steps = iteration_steps(tr, spans)
    if not steps:
        return None
    devs = sorted(tr.ops) or [0]
    idle = 0.0
    for d in devs:
        busy = tr.busy_intervals(d)
        for s in steps:
            ran = tracing.length(tracing.union(busy, (s.t0, s.t1)))
            idle += (s.t1 - s.t0 - ran) / len(devs)
    return 1e3 * idle / len(steps)


def step_idle_parts(tr, spans: list) -> dict:
    """Device idle seconds inside ``synera.step`` regions: in total, and
    with no child region of the step open (the step's own code)."""
    total = own = 0.0
    devs = sorted(tr.ops) or [0]
    steps = [s for s in spans if s.name == STEP]
    for d in devs:
        for dt, s, m in _idle_segments(tr, spans, d):
            if s is None or not any(p.t0 <= m < p.t1 for p in steps):
                continue
            total += dt / len(devs)
            if s.name == STEP:
                own += dt / len(devs)
    return {"idle_in_steps_s": total, "idle_in_step_itself_s": own,
            "named_child_share": (1.0 - own / total) if total > 0 else None}


def queue_waits(run, kind: str = "verify"):
    """``dispatch_ms - arrival_ms`` of every ``kind`` request the
    program's tracer recorded, whose arrival (its due time) lies in the
    window; None without a tracer that keeps queue records."""
    tracer = getattr(getattr(run, "server", None), "tracer", None)
    records = getattr(tracer, "queue_records", None)
    if records is None or not tracer.enabled:
        return None
    return [d - a for _, _, k, a, d in records()
            if k == kind and run.in_window(a)]
