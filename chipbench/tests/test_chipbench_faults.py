"""``correct`` comes out false when the timed path is broken underneath:
a step that returns its state unchanged, half of the batch left out,
and a token altered where it is produced, on one chip and on one
replica of a four-replica fleet; and when the control, the reference in
a lower precision, takes the program's place.  The same run with the
path intact comes out true."""
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from chipbench import harness, weights
from chipbench.tests.tiny import tiny_cell


def _state_unchanged(step):
    def f(params, cache, *args):
        out, _ = step(params, cache, *args)
        return out, cache
    return f


def _half_batch(step):
    def f(params, cache, tokens, pos, *args):
        keep = jnp.arange(pos.shape[0])[:, None] < pos.shape[0] // 2
        return step(params, cache, tokens, jnp.where(keep, pos, -1), *args)
    return f


def _token_altered(step, vocab):
    def f(*args):
        (tok, *rest), cache = step(*args)
        return ((tok + 1) % vocab, *rest), cache
    return f


FAULTS = {
    "intact": lambda step, vocab: step,
    "state_unchanged": lambda step, vocab: _state_unchanged(step),
    "half_batch": lambda step, vocab: _half_batch(step),
    "token_altered": _token_altered,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_correct_catches_a_broken_timed_path(monkeypatch, fault):
    make = engine_mod.make_cloud_verify_step
    cell = tiny_cell("mistral-7b", "chat")
    vocab = cell["model"]["vocab_size"]

    def broken(cfg, **kw):
        return FAULTS[fault](make(cfg, **kw), vocab)

    monkeypatch.setattr(engine_mod, "make_cloud_verify_step", broken)
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {})
    res = harness.execute(cell, 77_000_000_001, 2.0, False,
                          jax.devices()[:1], time.perf_counter())
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is (fault == "intact"), res["checks"]
    if fault != "intact":
        assert not gap["value"] <= gap["limit"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_control_in_the_programs_place_is_not_correct(seed):
    """The control (the reference with float8 matmul operands) serves its
    own first tokens at 1,800 positions of six sequences, through the
    harness's own comparison, at the cell's head size and vocabulary
    with four layers: not correct.  Tokens the float32 reference puts
    first are correct."""
    cell = tiny_cell("mistral-7b", "chat")
    m = cell["model"]
    m.update(hidden_size=1024, intermediate_size=3584, head_dim=128,
             num_attention_heads=8, num_key_value_heads=2,
             num_hidden_layers=4, vocab_size=32768)
    run = harness.Run(cell, seed, 1.0, False, jax.devices()[:1], 0.0)
    run.params = weights.make_params(run.cfg, seed, run.devices[0])
    run.unanswered, run.unfinished = 0, 0
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(1, 32768, 400).astype(np.int32) for _ in range(6)]
    ref = harness._load_reference(m["reference"])
    probe = [(t, [(p, 0) for p in range(100, 400)]) for t in seqs]
    best = [(t, [(p, int(row.argmax())) for p, row in
                 zip(range(100, 400), np.asarray(lg))])
            for t, lg in zip(seqs, ref.logits_at(m, run.params, probe,
                                                 run.devices))]
    run.check(best)
    assert run.correct is True
    low = ref.control_streams(m, run.params, best, run.devices)
    gap = run.check(low)["max_logit_gap"]
    assert run.correct is False
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "intact"])
def test_correct_catches_a_fault_on_one_replica_of_a_fleet(fault):
    """The fleet's check takes every replica's longest stream, so a fault
    on the last replica alone is not correct (the fleet intact is the
    rehearsal's ``fleet4`` case)."""
    from chipbench.tests.test_chipbench_rehearsal import (FOUR_DEVICES,
                                                          ROOT, _env)

    p = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.rehearse_fault", fault, "3"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=_env(**FOUR_DEVICES))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False, res["checks"]
    assert not gap["value"] <= gap["limit"]


def test_a_configuration_without_a_limit_is_never_correct():
    """A configuration whose ``max_logit_gap`` limit is not measured yet
    (``null``) reports the gap it reads, and no run of it is correct,
    even where every token is the reference's own first."""
    cell = tiny_cell("qwen2-7b", "documents")
    m = cell["model"]
    m["check"] = {"max_logit_gap": None}
    run = harness.Run(cell, 3, 1.0, False, jax.devices()[:1], 0.0)
    run.params = weights.make_params(run.cfg, 3, run.devices[0])
    run.unanswered, run.unfinished = 0, 0
    tokens = np.random.default_rng(3).integers(1, 256, 200).astype(np.int32)
    ref = harness._load_reference(m["reference"])
    probe = [(tokens, [(p, 0) for p in range(20, 200)])]
    (lg,) = ref.logits_at(m, run.params, probe, run.devices)
    best = [(tokens, [(p, int(row.argmax())) for p, row in
                      zip(range(20, 200), np.asarray(lg))])]
    gap = run.check(best)["max_logit_gap"]
    assert gap["value"] == 0.0 and gap["limit"] is None
    assert run.correct is False
