"""The program's own record read beside the harness's: idle time by
program region and the host gap per step on a synthetic trace, the queue
reader on synthetic records, and a tiny cell end to end with the
program's tracer attached (``chipbench/traced_run.py``)."""
import json
import subprocess
import sys
from types import SimpleNamespace

import pytest

from chipbench import program_trace as pt
from chipbench import tracing
from chipbench.harness import _load_reader
from chipbench.tests.test_chipbench_rehearsal import ROOT, _env
from repro.serving.link import SimClock
from repro.serving.trace import NULL_TRACER, Tracer


def _trace():
    ev, sp = tracing.Event, tracing.Span
    ops = {0: [ev("%fusion.1 = bf16[8] fusion(x)", 0.10, 0.30, 0),
               ev("%fusion.2 = bf16[8] fusion(x)", 0.50, 0.60, 0)]}
    spans = [sp("synera.step", 0.05, 0.40),            # a verify step
             sp("synera.sched.plan", 0.05, 0.08),
             sp("synera.engine.feed", 0.08, 0.35),
             sp("synera.engine.blocks", 0.08, 0.09),
             sp("synera.engine.dispatch", 0.09, 0.10),
             sp("synera.engine.sync", 0.10, 0.33),
             sp("synera.server.deliver", 0.36, 0.39),
             sp("synera.step", 0.45, 0.62),            # a prefill step
             sp("synera.engine.prefill", 0.46, 0.61),
             sp("synera.engine.dispatch", 0.46, 0.50),
             sp("synera.engine.sync", 0.50, 0.61),
             sp("synera.step", 0.70, 0.72)]            # nothing to run
    spans.sort(key=lambda s: (s.t0, -s.t1))
    return tracing.TraceData((0.0, 1.0), ops, {}, []), spans


def test_idle_by_the_innermost_program_region():
    tr, spans = _trace()
    idle = pt.idle_by_program_span(tr, spans)
    want = {pt.OUTSIDE: 0.46, "synera.step": 0.06,
            "synera.engine.dispatch": 0.05, "synera.engine.sync": 0.04,
            "synera.sched.plan": 0.03, "synera.server.deliver": 0.03,
            "synera.engine.feed": 0.02, "synera.engine.blocks": 0.01}
    assert idle == pytest.approx(want)
    assert list(idle)[0] == pt.OUTSIDE                 # largest first
    assert sum(idle.values()) == pytest.approx(tr.window_s - tr.busy_s)


def test_host_gap_per_step_that_ran_an_iteration():
    tr, spans = _trace()
    # (0.35 - 0.20) idle in the verify step, (0.17 - 0.10) in the
    # prefill step; the empty step does not count
    assert pt.step_host_gap_ms(tr, spans) == pytest.approx(110.0)
    parts = pt.step_idle_parts(tr, spans)
    assert parts["idle_in_steps_s"] == pytest.approx(0.24)
    assert parts["idle_in_step_itself_s"] == pytest.approx(0.06)
    assert parts["named_child_share"] == pytest.approx(0.75)
    run = SimpleNamespace(_program_spans=spans)
    assert _load_reader("step_host_gap_ms")(
        dict(run=run, trace=tr)) == pytest.approx(110.0)


def test_queue_reader_takes_verifies_due_in_the_window():
    tracer = Tracer(SimClock())
    tracer.queue(1, "verify", 10.0, 30.0)
    tracer.queue(2, "verify", 20.0, 21.0)
    tracer.queue(3, "verify", 150.0, 160.0)            # due after the window
    tracer.queue(4, "prefill", 0.0, 90.0)
    run = SimpleNamespace(server=SimpleNamespace(tracer=tracer),
                          in_window=lambda t: 0.0 <= t < 100.0)
    assert _load_reader("verify_queue_ms")(dict(run=run)) == 10.5


def test_program_readers_return_nothing_without_the_program_record():
    untraced = SimpleNamespace(server=SimpleNamespace(tracer=NULL_TRACER),
                               trace_path=None, in_window=lambda t: True)
    assert _load_reader("verify_queue_ms")(dict(run=untraced)) is None
    assert _load_reader("step_host_gap_ms")(
        dict(run=untraced, trace=None)) is None
    tr, _ = _trace()                   # a trace with no program regions
    assert _load_reader("step_host_gap_ms")(
        dict(run=SimpleNamespace(_program_spans=[]), trace=tr)) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_with_the_program_tracer(trace):
    """The program's counters equal the harness's wrapped counts over the
    window, every fed verify has one queue record taken at or after its
    arrival, and with a trace both program metrics are reported."""
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.rehearse_traced",
         "mistral-7b", "chat", str(trace)], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=_env())
    assert p.returncode == 0, p.stderr[-3000:]
    lines = {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
             for line in p.stdout.splitlines()
             if line.startswith("chipbench ")}
    counts = lines["program_counts"]
    for key in ("rows_computed", "rows_fed", "prompt_tokens", "prompt_fed"):
        program, wrapped = counts[key]
        assert program == wrapped > 0, key
    assert counts["fed_verifies"] > 0
    assert counts["fed_without_record"] == 0
    assert counts["dispatch_before_arrival"] == 0
    assert counts["verify_queue_n"] > 0 and counts["host_syncs"] > 0
    assert counts["table_uploads"] > 0
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    if trace:
        assert res["metrics"]["verify_queue_ms"]["value"] >= 0
        assert res["metrics"]["step_host_gap_ms"]["value"] > 0
        tr = lines["program_trace"]
        assert tr["iteration_steps"] > 0
        assert "synera.engine.sync" in tr["idle_by_program_span"]
        assert tr["regions"]["synera.step"] >= tr["iteration_steps"]
    else:
        assert "program_trace" not in lines
