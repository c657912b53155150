"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device op intervals per chip, the step programs, the
Pallas kernels, and the benchmark's host spans, all on the trace's one
clock.

A TPU plane is ``/device:TPU:<id>``; its ``XLA Ops`` line holds one event
per HLO op run on the core (the name is the op's HLO text) and its
``XLA Modules`` line one event per program run (``jit_<name>(<hash>)``).
Host spans are the ``bench.*`` events the harness opens with
``TraceAnnotation``; ``bench.traced`` spans the traced window.  In a
fleet, a replica's spans end in ``.r<i>`` (``bench.verify.r2``): the
reduction strips that and gives the span the chip of replica ``i``, and
the device time read inside it is that chip's alone.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

# what the host was doing, by the innermost benchmark span open
SPAN_NAMES = {
    "bench.verify": "engine.feed (upload, dispatch, host sync)",
    "bench.prefill": "engine.prefill (upload, dispatch, host sync)",
    "bench.step": "scheduler planning",
    "bench.open": "generator opening streams",
    "bench.wait": "waiting for arrivals",
    "bench.traced": "harness loop",
}


@dataclass
class Span:
    name: str
    t0: float          # seconds on the trace clock
    t1: float
    device: int | None = None   # the chip of the replica it served


REPLICA = re.compile(r"^(bench\.\w+)\.r(\d+)$")


@dataclass
class Event:
    name: str
    t0: float
    t1: float
    device: int


@dataclass
class TraceData:
    window: tuple                     # (t0, t1) of bench.traced
    ops: dict = field(default_factory=dict)       # device -> [Event]
    modules: dict = field(default_factory=dict)   # device -> [Event]
    spans: list = field(default_factory=list)     # [Span], host
    chips: list = field(default_factory=list)     # device ids used

    @property
    def devices(self) -> list:
        """The chips the run used, an idle one too; else those traced."""
        return sorted(self.chips) or sorted(self.ops) or [0]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, device: int) -> np.ndarray:
        """Union of the device's op intervals inside the window, as
        sorted, disjoint (start, end) rows."""
        return union([(e.t0, e.t1) for e in self.ops.get(device, [])],
                     self.window)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        return float(np.mean(self.busy_per_chip()))

    def busy_per_chip(self) -> list:
        return [length(self.busy_intervals(d)) for d in self.devices]

    def spans_named(self, *names) -> list:
        return [s for s in self.spans if s.name in names
                and s.t0 >= self.window[0] and s.t1 <= self.window[1]]

    def _on(self, events: dict, span: Span) -> list:
        """The events of the span's chip, or of every chip."""
        if span.device is not None:
            return events.get(span.device, [])
        return [e for d in events.values() for e in d]

    def modules_in(self, span: Span, prefix: str) -> list:
        return [m for m in self._on(self.modules, span)
                if m.name.startswith(prefix) and span.t0 <= m.t0 < span.t1]

    def ops_in(self, span: Span, match) -> list:
        return [o for o in self._on(self.ops, span)
                if span.t0 <= o.t0 < span.t1 and match(o.name)]

    def innermost(self, t: float) -> str:
        best = None
        for s in self.spans:
            if s.t0 <= t < s.t1 and (best is None
                                     or s.t1 - s.t0 < best.t1 - best.t0):
                best = s
        if best is None:
            return "outside spans"
        what = SPAN_NAMES.get(best.name, best.name)
        if best.device is None:
            return what
        return f"replica on TPU{best.device}: {what}"

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by program and op), and the
        longest idle gaps, named by what the host was doing."""
        tot: dict[str, float] = {}
        for d, evs in self.ops.items():
            mods = sorted(self.modules.get(d, []), key=lambda m: m.t0)
            starts = np.array([m.t0 for m in mods])
            for e in evs:
                spent = min(e.t1, self.window[1]) - max(e.t0, self.window[0])
                if spent <= 0 or op_label(e.name).startswith(CONTAINERS):
                    continue
                k = np.searchsorted(starts, e.t0, side="right") - 1
                mod = mods[k].name.split("(")[0] if k >= 0 and \
                    mods[k].t1 >= e.t0 else "?"
                key = f"{mod}/{op_label(e.name)}"
                tot[key] = tot.get(key, 0.0) + spent
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.devices:
            busy = self.busy_intervals(d)
            edges = np.concatenate([[self.window[0]], busy.ravel(),
                                    [self.window[1]]]).reshape(-1, 2)
            for a, b in edges:
                if b > a:
                    gaps.append((b - a, a, d))
        gaps.sort(reverse=True)
        idle = [[f"TPU{d}: {self.innermost(a + g / 2)}", g]
                for g, a, d in gaps[:top]]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}


# ops that only hold other ops (a scan's loop): their time is their
# body's, listed op by op
CONTAINERS = ("while.", "conditional.")


def op_label(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = hlo_text.split(" = ", 1)[0].lstrip("%")
    return head[:80]


def is_kernel(hlo_text: str) -> bool:
    """A Pallas (Mosaic) kernel: a custom call carrying kernel metadata.
    XLA's own custom calls (``AllocateBuffer``, ``ConcatBitcast``) name
    their target instead."""
    return "custom-call(" in hlo_text and "kernel_metadata" in hlo_text


def union(intervals, window) -> np.ndarray:
    lo, hi = window
    iv = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                if b > lo and a < hi)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, np.float64).reshape(-1, 2)


def length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def reduce(path: str, devices=None) -> TraceData:
    """Read the trace at ``path``; ``devices`` (ids, replica ``i`` on
    ``devices[i]``) limits the chips."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            dev = int(name.rsplit(":", 1)[1])
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = _events(line, dev)
                elif line.name == "XLA Modules":
                    modules[dev] = _events(line, dev)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(_span(e, devices))
    win = [s for s in spans if s.name == "bench.traced"]
    if win:
        window = (win[0].t0, win[0].t1)
    else:
        allev = [e for d in ops.values() for e in d]
        window = (min(e.t0 for e in allev), max(e.t1 for e in allev))
    return TraceData(window, ops, modules, spans, list(devices or []))


def _span(e, devices) -> Span:
    name, device = e.name, None
    m = REPLICA.match(name)
    if m:
        r = int(m.group(2))
        name, device = m.group(1), devices[r] if devices else r
    return Span(name, e.start_ns * 1e-9,
                (e.start_ns + e.duration_ns) * 1e-9, device)


def _events(line, dev: int) -> list:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9, dev)
            for e in line.events]
