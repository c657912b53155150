"""The streams that ``correct`` compares are the window's own arrivals,
whatever the verifier's speed: a run whose verifies take several times
longer checks the same streams and the same served tokens; a pool stream
still unfinished when the drain limit stops the run makes it not
correct; and no stream that arrived in warm-up or drain is sampled."""
import json
import time

import jax
import numpy as np

import repro.serving.engine as engine_mod
from chipbench import harness, replay
from chipbench.tests.tiny import tiny_cell

SEED = 3_000_000_019
STEP_SLEEP_S = 0.15      # the tiny step takes some 30 ms on the CPU


def _slowed(make):
    """The step program with a host sleep inside it, so each call of the
    compiled program takes ``STEP_SLEEP_S`` longer."""
    def slow_make(cfg, **kw):
        step = make(cfg, **kw)

        def slow_step(*args):
            jax.debug.callback(lambda: time.sleep(STEP_SLEEP_S))
            return step(*args)
        return slow_step
    return slow_make


def _execute(monkeypatch, capsys, slow: bool) -> tuple[dict, dict]:
    """One tiny run; its result and its ``chipbench check`` line."""
    with monkeypatch.context() as m:
        if slow:
            m.setattr(engine_mod, "make_cloud_verify_step",
                      _slowed(engine_mod.make_cloud_verify_step))
        m.setattr(harness, "peaks_for", lambda kind: {})
        capsys.readouterr()
        res = harness.execute(tiny_cell("mistral-7b", "chat"), SEED, 2.0,
                              False, jax.devices()[:1], time.perf_counter())
    lines = {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("chipbench ")}
    return res, lines


def test_the_checked_streams_do_not_depend_on_the_verifiers_speed(
        monkeypatch, capsys):
    fast, fast_log = _execute(monkeypatch, capsys, slow=False)
    slow, slow_log = _execute(monkeypatch, capsys, slow=True)
    assert fast["correct"] is True, fast["checks"]
    assert slow["correct"] is True, slow["checks"]
    assert (slow_log["serve"]["verify_p50_ms"]
            > fast_log["serve"]["verify_p50_ms"] + 1e3 * STEP_SLEEP_S)
    assert slow_log["check"]["checked"] == fast_log["check"]["checked"]
    assert (slow["checks"]["served_tokens"]
            == fast["checks"]["served_tokens"])
    for key in ("pool_streams", "pool_served_tokens"):
        assert slow_log["check"][key] == fast_log["check"][key]
    assert slow_log["check"]["unfinished_streams"] == 0


def test_a_pool_stream_unfinished_at_the_drain_limit_is_not_correct(
        monkeypatch, capsys):
    monkeypatch.setattr(harness, "DRAIN_S", 0.01)
    res, log = _execute(monkeypatch, capsys, slow=True)
    unfinished = res["checks"]["unfinished_streams"]
    assert unfinished["value"] >= 1
    assert unfinished["value"] > unfinished["limit"]
    assert res["correct"] is False
    assert log["check"]["unfinished_streams"] == unfinished["value"]
    assert log["serve"]["drain_s"] < 1.0


def _record(sid: int, start_ms: float, n_seq: int, n_served: int,
            due_ms: list) -> replay.StreamRecord:
    spec = replay.StreamSpec(sid, start_ms / 1e3, [1] * 8, 8,
                             np.zeros(4, bool), 0)
    rec = replay.StreamRecord(spec, start_ms)
    rec.calls = [replay.Call("verify", t, reply_ms=t + 1.0) for t in due_ms]
    rec.seq = [1] * n_seq
    rec.served = [(8 + i, 1) for i in range(n_served)]
    rec.done = True
    return rec


def test_sample_streams_never_takes_a_warm_up_or_drain_stream():
    """Warm-up and drain streams longer than any of the window's, and a
    warm-up stream with calls due in the window, are never sampled; the
    window's boundaries are ``w0 <= arrival < w1``."""
    cell = tiny_cell("mistral-7b", "chat")
    cell["mix"]["check_tokens"] = 10 ** 6       # take every pool stream
    run = harness.Run(cell, SEED, 10.0, False, jax.devices()[:1], 0.0)
    run.w0, run.w1 = 1000.0, 11000.0
    run.records = [
        _record(0, 0.0, 900, 300, [500.0, 1500.0, 4000.0]),   # warm-up
        _record(1, 999.9, 800, 200, [1200.0, 2000.0]),         # warm-up
        _record(2, 1000.0, 40, 3, [1100.0]),                   # window
        _record(3, 5000.0, 60, 5, [5100.0, 5300.0]),           # window
        _record(4, 10999.0, 30, 1, [11100.0]),                 # window
        _record(5, 11000.0, 1000, 400, [11100.0]),             # drain
        _record(6, 30000.0, 1000, 400, [30100.0]),             # drain
    ]
    run.records[3].done = False
    assert [r.spec.sid for r in run.pool()] == [2, 3, 4]
    assert len(run.sample_streams()) == 2 and run.checked == [2, 4]
    run.mix["check_tokens"] = 1
    assert len(run.sample_streams()) == 1 and run.checked == [2]
