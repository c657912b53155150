"""API load benchmark: Poisson arrivals over real sockets against the
OpenAI-compatible gateway (serving/gateway/), measuring what a client
actually observes — wall-clock TTFT, TPOT and E2E percentiles, goodput,
and 429 behaviour at the queue cap — plus the modeled-vs-real
cross-check the gateway's RealClock makes possible on the same run.

Each request is one blocking socket on its own thread (the container
has no HTTP client library): it sleeps until its Poisson arrival time,
POSTs a streaming chat completion, timestamps every SSE chunk, and
parses the streamed token ids back out.  Accepted streams are asserted
**byte-identical** to an in-process ``run_synera`` over the same
prompts (the gateway adds transport, not tokens); the summary records
the same ``outputs_sha`` digest serve.py prints.

Cross-check: the server serves at host speed while ``RealClock``
accumulates the modeled schedule as shadow time, so the summary reports
``wall_ms`` next to ``modeled_ms``.  Under ``--pace`` the engine sleeps
through modeled costs, making wall >= modeled with the excess being
host compute + transport (asserted in ``--check``; see
docs/serving_api.md for the tolerance discussion).

Usage:
  PYTHONPATH=src:. python -m benchmarks.api_bench \
      [--requests 24] [--rate 8] [--max-new 16] \
      [--max-active 4] [--queue-cap 8] [--pace] \
      [--out benchmarks/BENCH_api.json]
  PYTHONPATH=src:. python -m benchmarks.api_bench --check   # CI smoke
"""
from __future__ import annotations

import argparse
import json
import socket
import threading
import time

import numpy as np


# ---------------------------------------------------------------------
# minimal blocking HTTP/SSE client
# ---------------------------------------------------------------------

def _post_stream(port: int, prompt, max_new: int, timeout: float = 600.0):
    """POST one streaming chat completion; returns a per-request record
    with client-side wall timings (seconds, monotonic) per SSE chunk."""
    body = json.dumps({
        "model": "bench", "stream": True, "max_tokens": max_new,
        "messages": [{"role": "user",
                      "content": " ".join(str(t) for t in prompt)}],
    }).encode()
    head = (f"POST /v1/chat/completions HTTP/1.1\r\nHost: bench\r\n"
            f"Connection: close\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    rec = dict(status=0, tokens=[], t_send=time.monotonic(),
               t_first=None, t_last=None, t_done=None, retry_after=None)
    sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    try:
        sock.sendall(head + body)
        data = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
            if rec["t_first"] is None and b'"content"' in data:
                rec["t_first"] = time.monotonic()
        rec["t_done"] = time.monotonic()
    finally:
        sock.close()
    headtxt, _, payload = data.partition(b"\r\n\r\n")
    rec["status"] = int(headtxt.split(None, 2)[1])
    for ln in headtxt.decode("latin1").split("\r\n"):
        if ln.lower().startswith("retry-after:"):
            rec["retry_after"] = ln.split(":", 1)[1].strip()
    if rec["status"] != 200:
        return rec
    for frame in payload.split(b"\n\n"):
        if not frame.startswith(b"data: ") or frame == b"data: [DONE]":
            continue
        delta = json.loads(frame[6:])["choices"][0]["delta"]
        if "content" in delta:
            rec["tokens"] += [int(t) for t in delta["content"].split()]
            rec["t_last"] = rec["t_done"]
    return rec


def _get_json(port: int, path: str) -> dict:
    sock = socket.create_connection(("127.0.0.1", port), timeout=60)
    try:
        sock.sendall(f"GET {path} HTTP/1.1\r\nHost: b\r\n"
                     f"Connection: close\r\n\r\n".encode())
        data = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
    finally:
        sock.close()
    return json.loads(data.partition(b"\r\n\r\n")[2])


def _pcts(xs):
    if not xs:
        return {}
    return {f"p{q}": float(np.percentile(xs, q)) for q in (50, 90, 95, 99)}


# ---------------------------------------------------------------------
# the bench
# ---------------------------------------------------------------------

def run_bench(requests: int = 24, rate: float = 8.0, max_new: int = 16,
              max_active: int = 4, queue_cap: int = 8, pace: bool = False,
              seed: int = 0, burst: bool = False) -> dict:
    from benchmarks import paper_claims as PC
    from benchmarks.prepare import get_pair
    from repro.core.offload import OffloadPolicy
    from repro.serving import synergy as SY
    from repro.serving.gateway import Gateway, GatewayConfig
    from repro.serving.link import RealClock
    from repro.serving.server import SyneraServer

    slm_cfg, slm_p, llm_cfg, llm_p, task = get_pair()
    dev = PC.make_device(slm_cfg, slm_p, policy=OffloadPolicy(mode="all"),
                         use_early_exit=False)
    eng = PC.make_engine(llm_cfg, llm_p, slots=max_active)
    prompts = [p for p, _ in PC.eval_set(task, requests, seed=seed + 7)]

    # in-process reference: the gateway must stream these exact tokens
    ref = SY.run_synera(dev, eng, prompts, max_new, concurrency=1)
    import hashlib
    ref_sha = hashlib.sha256(json.dumps(
        [[int(t) for t in o] for o in ref.outputs]).encode()).hexdigest()[:16]

    server = SyneraServer(dev, eng, clock=RealClock(pace=pace),
                          clamp_arrivals=not pace)
    gw = Gateway(server, GatewayConfig(
        port=0, max_active=max_active, queue_cap=queue_cap,
        max_new_default=max_new, max_new_cap=max(max_new, 256)))
    gw.start()

    rng = np.random.default_rng(seed + 13)
    gaps = (np.zeros(requests) if burst
            else rng.exponential(1.0 / rate, requests))
    arrivals = np.cumsum(gaps)
    records: list = [None] * requests

    def _one(i):
        time.sleep(max(0.0, arrivals[i] - (time.monotonic() - t0)))
        records[i] = _post_stream(gw.port, prompts[i], max_new)

    try:
        t0 = time.monotonic()
        threads = [threading.Thread(target=_one, args=(i,))
                   for i in range(requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall_s = time.monotonic() - t0
        metrics = _get_json(gw.port, "/metrics?format=json")
    finally:
        gw.close()

    ok = [r for r in records if r["status"] == 200]
    rejected = [r for r in records if r["status"] == 429]
    mismatches = sum(1 for i, r in enumerate(records)
                     if r["status"] == 200
                     and r["tokens"] != [int(t) for t in ref.outputs[i]])
    ttft = [(r["t_first"] - r["t_send"]) * 1e3 for r in ok if r["t_first"]]
    e2e = [(r["t_done"] - r["t_send"]) * 1e3 for r in ok]
    tpot = [(r["t_last"] - r["t_first"]) / (len(r["tokens"]) - 1) * 1e3
            for r in ok if r["t_last"] and len(r["tokens"]) > 1]
    n_tokens = sum(len(r["tokens"]) for r in ok)

    return dict(
        config=dict(requests=requests, rate_rps=None if burst else rate,
                    burst=burst, max_new=max_new, max_active=max_active,
                    queue_cap=queue_cap, pace=pace, seed=seed),
        wall_s=wall_s,
        accepted=len(ok),
        rejected_429=len(rejected),
        retry_after_present=all(r["retry_after"] is not None
                                for r in rejected),
        goodput_rps=len(ok) / wall_s,
        goodput_tok_s=n_tokens / wall_s,
        ttft_ms=dict(mean=float(np.mean(ttft)) if ttft else 0.0,
                     **_pcts(ttft)),
        tpot_ms=dict(mean=float(np.mean(tpot)) if tpot else 0.0,
                     **_pcts(tpot)),
        e2e_ms=dict(mean=float(np.mean(e2e)) if e2e else 0.0,
                    **_pcts(e2e)),
        identity=dict(outputs_sha=ref_sha, mismatched_streams=mismatches),
        # modeled-vs-real cross-check: both clocks from the same run
        cross_check=dict(
            wall_ms=wall_s * 1e3,
            modeled_ms=metrics["modeled_ms"],
            wall_over_modeled=wall_s * 1e3 / max(metrics["modeled_ms"], 1e-9),
            server_ttft_modeled_p50=metrics["ttft_ms_p50"],
            server_e2e_modeled_p50=metrics["e2e_ms_p50"]),
        server=dict(completed_streams=metrics["completed_streams"],
                    cancelled_streams=metrics["cancelled_streams"],
                    rejected_requests=metrics["rejected_requests"],
                    iterations=metrics["iterations"],
                    mean_verify_occupancy=metrics["mean_verify_occupancy"]),
    )


def check(res: dict) -> None:
    """CI assertions over a saturating burst run (see ci.yml)."""
    assert res["accepted"] >= 1, res
    assert res["identity"]["mismatched_streams"] == 0, \
        "streamed tokens diverged from the in-process reference"
    assert res["rejected_429"] >= 1, \
        f"queue cap never tripped: {res['rejected_429']} rejections"
    assert res["retry_after_present"], "429 without Retry-After"
    assert res["server"]["rejected_requests"] == res["rejected_429"], res
    assert res["cross_check"]["modeled_ms"] > 0, res
    if res["config"]["pace"]:
        # paced: the engine sleeps through modeled costs, so wall time
        # must dominate the modeled schedule
        assert res["cross_check"]["wall_over_modeled"] >= 1.0, res
    assert res["ttft_ms"]["p50"] > 0 and res["e2e_ms"]["p95"] > 0, res
    print("api_bench --check: all assertions passed")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests/s of wall time")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-active", type=int, default=4)
    ap.add_argument("--queue-cap", type=int, default=8)
    ap.add_argument("--pace", action="store_true",
                    help="pace the engine to the modeled schedule "
                         "(wall latencies track modeled ones)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--burst", action="store_true",
                    help="all requests arrive at t=0 (saturation test)")
    ap.add_argument("--check", action="store_true",
                    help="CI smoke: small saturating burst, assert 429 "
                         "at the cap + streamed-token identity")
    ap.add_argument("--out", default="benchmarks/BENCH_api.json")
    args = ap.parse_args()

    if args.check:
        # one lone streaming request first: must be accepted, never
        # rejected, and byte-identical to the in-process reference
        solo = run_bench(requests=1, max_new=8, max_active=2, queue_cap=1,
                         pace=args.pace, seed=args.seed, burst=True)
        assert solo["accepted"] == 1 and solo["rejected_429"] == 0, solo
        assert solo["identity"]["mismatched_streams"] == 0, solo
        print("api_bench --check: solo stream ok")
        res = run_bench(requests=8, max_new=8, max_active=2, queue_cap=1,
                        pace=args.pace, seed=args.seed, burst=True)
        res["solo"] = solo
    else:
        res = run_bench(requests=args.requests, rate=args.rate,
                        max_new=args.max_new, max_active=args.max_active,
                        queue_cap=args.queue_cap, pace=args.pace,
                        seed=args.seed, burst=args.burst)
        if not args.pace:
            # compact paced companion: the engine sleeps through modeled
            # costs, so wall >= modeled must hold (the strict direction
            # of the cross-check; unpaced only yields the ratio)
            paced = run_bench(requests=6, rate=args.rate, max_new=8,
                              max_active=args.max_active,
                              queue_cap=args.queue_cap, pace=True,
                              seed=args.seed)
            assert paced["cross_check"]["wall_over_modeled"] >= 1.0, paced
            res["paced"] = paced
    print(json.dumps(res, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.out}")
    if args.check:
        check(res)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
