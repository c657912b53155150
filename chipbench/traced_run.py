"""``chipbench/run.py`` with the serving program's tracer attached: to
delete once ``harness.Run.build`` hands the server a ``Tracer`` under
``--trace 1`` (PERF.md, Open questions).

    python3 chipbench/traced_run.py --workload mistral-7b.chat \\
        --seed 7 --seconds 51 --trace 1

runs one cell exactly as ``chipbench/run.py`` does (same options, same
result line) with a ``Tracer`` on the server's clock, and prints two
more lines: ``chipbench program_counts`` (the program's counters, as
``ServerStats`` exposes them, beside the harness's wrapped counts, and
the queue records) and, with ``--trace 1``, ``chipbench program_trace``
(device idle time by the innermost program region open, and the count
of each region; ``chipbench/program_trace.py``).  With ``--trace 1`` the
result's metrics also carry ``verify_queue_ms`` and ``step_host_gap_ms``
(``chipbench/metrics/``).
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness  # noqa: E402
from chipbench.program_trace import (STEP, idle_by_program_span,  # noqa: E402
                                     iteration_steps, queue_waits, spans_of,
                                     step_idle_parts)

# readers of chipbench/metrics/ that read what the program records
METRICS = {"verify_queue_ms": "ms", "step_host_gap_ms": "ms"}
# the program's counters, read as ServerStats (and so /metrics) has them
PROGRAM = ("rows_computed", "rows_fed", "prompt_tokens_done",
           "prefill_fed_tokens", "table_uploads", "table_upload_bytes",
           "host_syncs")


class TracedRun(harness.Run):
    """``harness.Run`` whose server carries a ``Tracer`` on the run's
    clock (built after the warm-up, so only served traffic is
    recorded)."""

    def build(self):
        from repro.serving.server import SyneraServer
        from repro.serving.trace import Tracer

        super().build()
        self.tracer = Tracer(self.clock)
        self.server = SyneraServer(self.device, self.engine,
                                   chunk=self.sv["chunk"], clock=self.clock,
                                   tracer=self.tracer)
        self._watch_prefills()

    def _counters(self) -> dict:
        out = super()._counters()
        st = self.server.server_stats()
        out.update({f"program_{k}": getattr(st, k) for k in PROGRAM})
        return out

    def end_to_end(self) -> dict:
        out = super().end_to_end()
        d = {k: self.end[k] - self.base[k] for k in self.end}
        c = self.counting.log
        waits = queue_waits(self) or []
        fed = {r for name, *_, args in self.tracer.regions()
               if name == STEP and args.get("kind") == "verify"
               for r in args["rids"]}
        recorded = {rid: (a, t) for _, rid, k, a, t in
                    self.tracer.queue_records() if k == "verify"}
        harness.log(
            "program_counts",
            rows_computed=[d["program_rows_computed"], c.rows_computed],
            rows_fed=[d["program_rows_fed"], c.rows_valid],
            prompt_tokens=[d["program_prompt_tokens_done"],
                           c.prompt_tokens],
            prompt_fed=[d["program_prefill_fed_tokens"], c.prompt_fed],
            table_uploads=d["program_table_uploads"],
            table_upload_bytes=d["program_table_upload_bytes"],
            host_syncs=d["program_host_syncs"],
            verify_queue_n=len(waits),
            verify_queue_p50_ms=harness.percentile(waits, 50)
            if waits else None,
            verify_queue_p95_ms=harness.percentile(waits, 95)
            if waits else None,
            fed_verifies=len(fed),
            fed_without_record=len(fed - set(recorded)),
            dispatch_before_arrival=sum(
                1 for a, t in recorded.values() if t < a),
            regions=len(self.tracer.regions()))
        return out

    def per_layer(self) -> dict:
        out = super().per_layer()
        for name, unit in METRICS.items():
            v = harness._load_reader(name)(dict(run=self,
                                                trace=self.trace_data))
            if v is not None:
                out[name] = {"value": float(v), "unit": unit}
        tr = self.trace_data
        if tr is not None:
            spans = spans_of(self)
            w0, w1 = tr.window
            inside = [s for s in spans if s.t0 >= w0 and s.t1 <= w1]
            harness.log(
                "program_trace",
                idle_by_program_span=idle_by_program_span(tr, spans),
                **step_idle_parts(tr, spans),
                iteration_steps=len(iteration_steps(tr, spans)),
                regions={n: sum(1 for s in inside if s.name == n)
                         for n in sorted({s.name for s in inside})})
        return out


def main() -> int:
    from chipbench import run

    # run.py's own command, its setup counted from this process's start
    harness.Run = TracedRun
    run.T_PROCESS0 = T_PROCESS0
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
