"""The replay generator: the same seed gives the same schedule and the
same calls; another seed the same work in another order."""
import numpy as np
import pytest

from chipbench import cells, replay
from repro.core.verifier import VerifyResult
from repro.serving.device import CloudReply

SEED = 3_000_000_019          # above 32 bits, as seeds may be


def _mix(name):
    return cells.load_json(cells.HERE / "traffic" / f"{name}.json")


@pytest.mark.parametrize("seed", [SEED, 7])
def test_same_seed_same_schedule(seed):
    a = replay.schedule(_mix("chat"), seed, [5.0, 30.0], 32768, 2048)
    b = replay.schedule(_mix("chat"), seed, [5.0, 30.0], 32768, 2048)
    assert len(a) == len(b) > 10
    for x, y in zip(a, b):
        assert (x.arrival_s, x.prompt, x.max_new, x.draft_seed) == \
            (y.arrival_s, y.prompt, y.max_new, y.draft_seed)
        assert np.array_equal(x.offload, y.offload)
        assert 1 <= min(x.prompt) and max(x.prompt) < 32768
        assert len(x.prompt) + x.max_new + 4 <= 2048


@pytest.mark.parametrize("part", [0, 1])
def test_another_seed_draws_the_same_work_in_another_order(part):
    mix = _mix("chat")
    a, b = replay.draws(mix, 1, 500, part), replay.draws(mix, 2, 500, part)
    for k in ("prompt_len", "out_len"):
        assert sorted(a[k].tolist()) == sorted(b[k].tolist())
        assert a[k].tolist() != b[k].tolist()
    assert sorted(map(tuple, a["offload"].tolist())) == \
        sorted(map(tuple, b["offload"].tolist()))


def _drive(spec, now=lambda: 0.0):
    """Run one replayed stream against a verifier that always rejects
    the draft and corrects it with token 7."""
    dev = replay.ReplayDevice(_mix("chat"), 32768, now)
    rec = replay.StreamRecord(spec, start_ms=0.0)
    dev.pending = rec
    gen = dev.generate_steps(spec.prompt, spec.max_new)
    calls, reply = [], None
    try:
        while True:
            call = gen.send(reply)
            calls.append((call.kind, call.arrival_ms, call.seq and
                          len(call.seq), call.draft))
            reply = None if call.kind == "prefill" else CloudReply(
                result=VerifyResult(0, 7, None, [7]))
    except StopIteration as e:
        return calls, e.value.tokens, rec


def test_replayed_stream_yields_the_same_calls_for_the_same_seed():
    spec = replay.schedule(_mix("chat"), SEED, [30.0], 32768, 2048)[0]
    a, toks_a, rec = _drive(spec)
    b, toks_b, _ = _drive(spec)
    assert a == b and toks_a == toks_b
    assert a[0][0] == "prefill" and len(toks_a) == spec.max_new
    verifies = [c for c in a if c[0] == "verify"]
    # every verify feeds the tokens the cloud lacks plus one draft chunk
    assert [c.rows for c in rec.calls[1:]] == [
        (c[2] - len(spec.prompt)) - (prev_cached - len(spec.prompt)) + 4
        for c, prev_cached in zip(verifies, _cached(verifies, spec))]
    assert all(tok == 7 for _, tok in rec.served)


def _cached(verifies, spec):
    """The cloud's frontier before each verify: the prompt, then the
    previous verify's accepted stream (no draft accepted)."""
    out, cached = [], len(spec.prompt)
    for c in verifies:
        out.append(cached)
        cached = c[2]            # len(seq) at that verify; draft rejected
    return out


@pytest.mark.parametrize("seed", [1, 2])
def test_every_seed_gets_the_same_window(seed):
    """The streams arriving in the window are one fixed set of sizes and
    offload patterns whatever the seed; only order and tokens differ."""
    mix = _mix("chat")
    win = [s for s in replay.schedule(mix, seed, [12.0, 57.0, 117.0],
                                      32768, 2048)
           if 12.0 <= s.arrival_s < 57.0]
    ref = [s for s in replay.schedule(mix, 99, [12.0, 57.0, 117.0],
                                      32768, 2048)
           if 12.0 <= s.arrival_s < 57.0]
    key = lambda s: (len(s.prompt), s.max_new, s.offload.tobytes())  # noqa
    assert len(win) == round(mix["arrival"]["rate_per_s"] * 45)
    assert sorted(map(key, win)) == sorted(map(key, ref))
    assert [s.prompt for s in win] != [s.prompt for s in ref]


@pytest.mark.parametrize("seed", [1, 2_000_000_011])
def test_arrivals_are_poisson_given_their_count(seed):
    """Within a part, the arrival times are independent and uniform, so
    the gaps between them are close to exponential at the mix's rate:
    mean 1/rate and a coefficient of variation near 1 (a regular or
    balanced schedule would read far less)."""
    rate = 1.0
    t = replay.arrivals({"arrival": {"kind": "poisson",
                                     "rate_per_s": rate}},
                        seed, 0.0, 4000.0, 4000, 1)
    assert len(t) == 4000 and (np.diff(t) >= 0).all()
    assert 0.0 <= t[0] and t[-1] < 4000.0
    gaps = np.diff(t)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.06)
    other = replay.arrivals({"arrival": {"kind": "poisson",
                                         "rate_per_s": rate}},
                            seed + 1, 0.0, 4000.0, 4000, 1)
    assert not np.array_equal(t, other)


def test_lengths_keep_the_mix_distribution():
    """Each stream's prompt length is a draw of the mix's lognormal: the
    stratified set has the distribution's median and quartiles."""
    mix = _mix("chat")
    n = 1001
    lens = replay.draws(mix, 5, n, 1)["prompt_len"]
    p = mix["prompt"]
    assert np.median(lens) == pytest.approx(p["median"], abs=1)
    q1, q3 = np.percentile(lens, [25, 75])
    z = 0.6744897501960817        # the standard normal's third quartile
    assert q1 == pytest.approx(p["median"] * np.exp(-p["sigma"] * z), rel=0.01)
    assert q3 == pytest.approx(p["median"] * np.exp(p["sigma"] * z), rel=0.01)
    assert lens.min() >= p["min"] and lens.max() <= p["max"]


def test_offload_budget_sets_the_verify_share():
    specs = replay.schedule(_mix("chat"), 5, [200.0], 32768, 2048)
    share = np.mean([s.offload[:64].mean() for s in specs])
    assert share == pytest.approx(_mix("chat")["offload_budget"], abs=0.02)
