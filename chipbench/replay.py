"""Traffic from a mix file, and the device streams that replay it.

One general generator reads a traffic mix (``chipbench/traffic/*.json``)
and makes the arrival schedule.  The warm-up, the window and the drain
each get a fixed set of streams (sizes as quantiles of the mix's
distributions, offload patterns from a fixed base seed), in an order,
with token contents and with Poisson arrival times that the run's seed
chooses; so a seed changes which stream is which and when it comes, not
how much work a window holds.

A prompt is either a length drawn from the mix (``lognormal``,
``uniform``) or, for ``documents``, one of a fixed set of shared
documents followed by the stream's own question: the seed writes the
documents' tokens, their lengths are a fixed set, and each part's
streams ask about them in fixed numbers that follow Zipf popularity.

``ReplayDevice`` stands in for ``DeviceRuntime`` on the cloud's side of
the protocol: ``generate_steps`` yields the ``CloudCall``s a phone would
send (the prompt prefill at arrival, then one verify per offloaded
chunk) and consumes the ``CloudReply``s.  Drafting is paced with the
paper's device constants and the WAN link with ``LinkModel``'s, on the
stream's own timeline; the verifier's time is wall time.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

BASE_SEED = 0x5EED


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution (whole numbers
    within [min, max])."""
    q = (np.arange(n) + 0.5) / n
    if dist["kind"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["kind"] == "uniform":
        v = dist["min"] + (dist["max"] - dist["min"]) * q
    else:
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


@dataclass
class StreamSpec:
    sid: int
    arrival_s: float              # offset from the start of traffic
    prompt: list                  # token ids
    max_new: int
    offload: np.ndarray           # offload decision per draft chunk
    draft_seed: int


def zipf_ranks(n_docs: int, s: float, n: int) -> np.ndarray:
    """``n`` stratified picks of a document's popularity rank (0 the most
    asked about), rank ``r`` with weight ``1 / (r + 1) ** s``: each rank
    gets its share of the picks, rounded, on every seed."""
    w = 1.0 / np.arange(1, n_docs + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w) / w.sum()
    q = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, q, side="right"), n_docs - 1)


def documents(mix: dict, seed: int, vocab: int) -> list[list[int]] | None:
    """The seed's shared documents in order of popularity, or None where
    the mix shares none.  Their lengths are one fixed set (quantiles of
    the mix's document length), paired with the ranks by the base seed,
    so every seed asks as much of the cache; the tokens are the seed's."""
    p = mix["prompt"]
    if p["kind"] != "documents":
        return None
    n = p["documents"]
    lens = _quantiles(p["length"], n)[
        np.random.default_rng([BASE_SEED, 1 << 20]).permutation(n)]
    rng = np.random.default_rng([int(seed) % (1 << 63), 5])
    return [list(map(int, rng.integers(1, vocab, int(L)))) for L in lens]


def draws(mix: dict, seed: int, n: int, part: int = 0) -> dict:
    """The sizes and offload patterns of ``n`` streams: one fixed set for
    part ``part`` of the schedule (quantiles of the mix's distributions,
    offload patterns from the base seed), in the order ``seed`` chooses.
    Each stream's lengths keep the mix's distributions; for shared
    documents, a stream draws the rank of its document (Zipf) and the
    length of its question in place of a prompt length."""
    base = np.random.default_rng([BASE_SEED, part])
    run = np.random.default_rng([int(seed) % (1 << 63), 1, part])
    p = mix["prompt"]
    if p["kind"] == "documents":
        out = {"doc": zipf_ranks(p["documents"], p["zipf_s"], n),
               "question_len": _quantiles(p["question"], n)}
    else:
        out = {"prompt_len": _quantiles(p, n)}
    out.update(out_len=_quantiles(mix["output"], n),
               offload=base.random((n, mix["output"]["max"] + mix["gamma"]))
               < mix["offload_budget"])
    # a fixed pairing of the attributes into streams (base seed), then
    # the seed's order of the streams
    out = {k: v[base.permutation(n)] for k, v in out.items()}
    order = run.permutation(n)
    out = {k: v[order] for k, v in out.items()}
    out["rng"] = run
    return out


def arrivals(mix: dict, seed: int, lo: float, hi: float, n: int,
             part: int) -> np.ndarray:
    """Poisson arrivals in ``[lo, hi)`` conditioned on their count ``n``:
    given the count, a Poisson process's arrival times are independent
    and uniform over the interval, so bursts and lulls stay as the
    process makes them while every seed sends the same number."""
    if mix["arrival"]["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {mix['arrival']['kind']!r}")
    rng = np.random.default_rng([int(seed) % (1 << 63), 3, part])
    return np.sort(rng.uniform(lo, hi, n))


def _streams(mix: dict, d: dict, times, docs, vocab: int, s_max: int,
             first_sid: int) -> list[StreamSpec]:
    out = []
    for i, t in enumerate(times):
        if docs is None:
            prompt = list(map(int, d["rng"].integers(
                1, vocab, int(d["prompt_len"][i]))))
        else:
            prompt = docs[d["doc"][i]] + list(map(int, d["rng"].integers(
                1, vocab, int(d["question_len"][i]))))
        # the cache holds prompt + output + one draft chunk at most
        max_new = int(min(d["out_len"][i],
                          s_max - mix["gamma"] - len(prompt)))
        out.append(StreamSpec(first_sid + i, float(t), prompt, max_new,
                              d["offload"][i],
                              int(d["rng"].integers(1 << 62))))
    return out


def schedule(mix: dict, seed: int, edges_s: list, vocab: int,
             s_max: int) -> list[StreamSpec]:
    """Streams arriving in ``[0, edges_s[-1])`` for ``seed``.  Each part
    between consecutive edges (warm-up, window, drain) holds its own
    fixed set of ``rate x length`` streams, so every seed's window holds
    the same work, arriving as a Poisson process."""
    rate = mix["arrival"]["rate_per_s"]
    docs = documents(mix, seed, vocab)
    out = []
    lo = 0.0
    for part, hi in enumerate(edges_s):
        n = max(1, int(round(rate * (hi - lo))))
        out += _streams(mix, draws(mix, seed, n, part),
                        arrivals(mix, seed, lo, hi, n, part), docs, vocab,
                        s_max, len(out))
        lo = hi
    return out


PRIME_PART = 1 << 10          # the draws of the priming streams


def prime(mix: dict, seed: int, vocab: int, s_max: int,
          first_sid: int) -> list[StreamSpec]:
    """Where the mix says ``warm_documents``: one stream per document, in
    order of popularity, each with its own question, that the warm-up
    opens together before any arrival, so that the fleet holds every
    document as a deployment's would.  Otherwise none."""
    docs = documents(mix, seed, vocab)
    if docs is None or not mix.get("warm_documents"):
        return []
    d = draws(mix, seed, len(docs), PRIME_PART)
    d["doc"] = np.arange(len(docs))
    return _streams(mix, d, np.zeros(len(docs)), docs, vocab, s_max,
                    first_sid)


# ---------------------------------------------------------------------------
# Replayed device streams
# ---------------------------------------------------------------------------

@dataclass
class Call:
    kind: str                     # "prefill" | "verify"
    due_ms: float                 # absolute arrival given to the server
    reply_ms: float | None = None  # host time the reply reached the stream
    rows: int = 0                 # tokens the verify feeds (uncached + draft)


@dataclass
class StreamRecord:
    spec: StreamSpec
    start_ms: float
    calls: list = field(default_factory=list)
    emits: list = field(default_factory=list)     # (abs ms, tokens)
    served: list = field(default_factory=list)    # (position, token)
    seq: list = field(default_factory=list)
    session: object = None
    done: bool = False
    replica: int = 0              # the replica serving it (-1: degraded)


@dataclass
class _Dist:
    idx: np.ndarray
    val: np.ndarray


class _Timeline:
    """The stream-relative time the server reads off a finished stream
    (``DeviceMetrics.timeline`` shape)."""

    def __init__(self):
        self.t_ms = 0.0
        self.compute_ms = self.cloud_ms = self.link_ms = self.queue_ms = 0.0
        self.batch_wait_ms = self.swap_ms = self.preempted_ms = 0.0
        self.other_ms = 0.0

    def buckets(self) -> dict:
        return {"device": self.compute_ms}


@dataclass
class ReplayMetrics:
    tokens: list
    timeline: _Timeline


class ReplayDevice:
    """``DeviceRuntime``'s protocol, replayed from a ``StreamSpec``.

    The harness sets ``pending`` to the stream's record right before it
    opens the session; ``generate_steps`` binds it.  ``now_ms`` is the
    shared clock's reading, taken when a reply reaches the stream."""

    def __init__(self, mix: dict, vocab: int, now_ms):
        self.gamma = mix["gamma"]
        self.chunk_ms = self.gamma * (mix["device"]["ms_fixed"]
                                      + mix["device"]["ms_per_token"])
        self.bw_bits_per_ms = mix["link"]["bandwidth_mbps"] * 1e3
        self.half_rtt_ms = mix["link"]["rtt_ms"] / 2.0
        self.vocab = vocab
        self.now_ms = now_ms
        self.pending: StreamRecord | None = None

    def link_ms(self, nbytes: int) -> float:
        return self.half_rtt_ms + nbytes * 8.0 / self.bw_bits_per_ms

    def generate_steps(self, prompt, max_new: int, *, use_cloud: bool = True,
                       profile_mode: bool = False, emit=None, trace=None):
        rec, self.pending = self.pending, None
        if rec is None:
            raise RuntimeError("ReplayDevice: no stream record pending")
        return self._steps(rec, max_new, use_cloud, emit)

    def _steps(self, rec: StreamRecord, max_new: int, use_cloud: bool, emit):
        spec = rec.spec
        rng = np.random.default_rng(spec.draft_seed)
        seq = list(spec.prompt)
        T = len(seq)
        t = 0.0
        emitted = 0

        def flush():
            nonlocal emitted
            vis = min(len(seq) - T, max_new)
            if vis > emitted:
                if emit is not None:
                    emit(seq[T + emitted:T + vis], t)
                rec.emits.append((rec.start_ms + t, vis - emitted))
                emitted = vis

        if use_cloud:
            up = self.link_ms(4 * T + 32)
            rec.calls.append(Call("prefill", rec.start_ms + up))
            yield _cloud_call("prefill", 0.0, up, prompt=list(spec.prompt))
        cached = T if use_cloud else 0      # what the cloud holds of seq
        chunk = 0
        while len(seq) - T < max_new:
            draft = [int(x) for x in rng.integers(1, self.vocab, self.gamma)]
            t += self.chunk_ms
            offload = use_cloud and bool(spec.offload[chunk])
            chunk += 1
            if not offload:
                seq.extend(draft)
                flush()
                continue
            rows = len(seq) - cached + len(draft)
            up = self.link_ms(32 + 4 * rows + 8 * len(draft))
            call = Call("verify", rec.start_ms + t + up, rows=rows)
            rec.calls.append(call)
            reply = yield _cloud_call(
                "verify", t, up, seq=list(seq), draft=draft,
                dists=[_Dist(np.array([d]), np.array([1.0], np.float32))
                       for d in draft])
            call.reply_ms = self.now_ms()
            res = reply.result
            for i, tok in enumerate(res.tokens):
                rec.served.append((len(seq) + i, int(tok)))
            cached = len(seq) + res.n_accepted
            seq.extend(int(x) for x in res.tokens)
            t = (call.reply_ms - rec.start_ms) + self.link_ms(
                32 + 4 * (len(res.tokens) + 1))
            flush()
        rec.seq = seq
        rec.done = True
        tl = _Timeline()
        tl.t_ms = tl.compute_ms = t
        return ReplayMetrics(seq[T:T + max_new], tl)


def _cloud_call(kind, send_ms, uplink_ms, **kw):
    from repro.serving.device import CloudCall

    return CloudCall(kind, send_ms=send_ms, uplink_ms=uplink_ms, **kw)
