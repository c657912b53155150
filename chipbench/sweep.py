"""Find a cell's knee: the cell's set-up and window at each of several
fixed arrival rates and seeds, in one process on the chip.

    python3 chipbench/sweep.py --workload mistral-7b.chat \
        --rates 0.5 0.75 1 1.25 1.5 --seeds 5 6

Prints one ``chipbench sweep {json}`` line per rate and seed with the
end-to-end metrics, the prompt prefills and verifies queued at the
window's start and end, and the streams' lifetimes; then, as the last
line, for each rate the worst of its seeds and whether every seed met
the latency limits without a growing backlog.  The rate written into
the cell's traffic file is about four fifths of the highest rate that
meets them.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# what a phone's user tolerates (PERF.md section 2), in ms
LIMITS_MS = {"verify_p50_ms": 500.0, "verify_p95_ms": 3000.0,
             "prefill_p90_ms": 5000.0}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    from chipbench import cells, harness
    from chipbench.run import use_cache

    base = cells.cell(args.workload)
    seconds = args.seconds or cells.benchmark()["run_seconds"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < base["chips"]:
        print("chipbench sweep: needs the cell's chips", file=sys.stderr)
        return 2
    use_cache(jax)
    summary = {}
    for rate in args.rates:
        for seed in args.seeds:
            cell = copy.deepcopy(base)
            cell["mix"]["arrival"]["rate_per_s"] = rate
            run = harness.Run(cell, seed, seconds, False,
                              devices[:cell["chips"]], time.perf_counter())
            run.build()
            run.serve()
            e2e = run.end_to_end()
            life = run.lifetimes_s() or [float("nan")]
            grew = (run.end["queued_prefills"] > run.base["queued_prefills"]
                    + 1)
            meets = not grew and run.failed == 0 and all(
                e2e.get(k, float("inf")) <= v for k, v in LIMITS_MS.items())
            row = dict(rate_per_s=rate, seed=seed, attempted=run.attempted,
                       failed=run.failed, unanswered=run.unanswered,
                       samples=run.samples,
                       queued_at_start=run.base["queued_prefills"],
                       queued_at_end=run.end["queued_prefills"],
                       lifetime_p50_s=harness.percentile(life, 50),
                       lifetime_p95_s=harness.percentile(life, 95),
                       lifetime_max_s=max(life),
                       lateness_max_ms=max(run.lateness_ms),
                       meets_limits=meets, **e2e)
            harness.log("sweep", **row)
            s = summary.setdefault(str(rate), {"meets_limits": True})
            s["meets_limits"] &= meets
            for k in LIMITS_MS:
                s[k] = max(s.get(k, 0.0), e2e.get(k, float("inf")))
            s["stream_tokens_per_s"] = min(
                s.get("stream_tokens_per_s", float("inf")),
                e2e["stream_tokens_per_s"])
            run.free_program()
            del run
            gc.collect()
    ok = [float(r) for r, s in summary.items() if s["meets_limits"]]
    print(json.dumps({"workload": args.workload, "seconds": seconds,
                      "limits_ms": LIMITS_MS,
                      "highest_rate_meeting_limits": max(ok) if ok else None,
                      "worst_of_seeds": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
