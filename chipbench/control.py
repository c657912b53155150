"""Readings that set the limits of ``correct`` for a cell, and the
control run through the benchmark's own comparison.

    python3 chipbench/control.py --workload mistral-7b.chat \
        --seconds 51 --seeds 11 12 13 ...

For each seed, in one process on the chip: the cell's own set-up, load
and window, then on the sampled streams (a) the program's check, as a
run makes it, and (b) the same check with the control in the program's
place: each served token replaced by the one that the reference,
computed with float8 (e4m3) matmul operands, puts first at that
position.  The control has to come out not correct.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def readings(cell: dict, seed: int, seconds: float, devices) -> dict:
    from chipbench import harness

    run = harness.Run(cell, seed, seconds, False, devices,
                      time.perf_counter())
    run.build()
    run.serve()
    run.end_to_end()
    streams = run.sample_streams()
    out = {"seed": seed, "streams": len(streams)}
    t = time.perf_counter()
    out["program"] = {k: c["value"] for k, c in run.check(streams).items()}
    out["program"]["correct"] = run.correct
    out["reference_s"] = time.perf_counter() - t
    ref = harness._load_reference(cell["model"]["reference"])
    low = ref.control_streams(cell["model"], run.params, streams, devices)
    out["control"] = {k: c["value"] for k, c in run.check(low).items()}
    out["control"]["correct"] = run.correct
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    from chipbench import cells, harness
    from chipbench.run import use_cache

    cell = cells.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("chipbench control: needs the cell's chips", file=sys.stderr)
        return 2
    use_cache(jax)
    rows = []
    for seed in args.seeds:
        r = readings(cell, seed, args.seconds
                     or cells.benchmark()["run_seconds"],
                     devices[:cell["chips"]])
        rows.append(r)
        harness.log("control", **r)
        gc.collect()
    print(json.dumps({
        "workload": args.workload, "seeds": [r["seed"] for r in rows],
        "program_max_gap": max(r["program"]["max_logit_gap"] for r in rows),
        "control_min_gap": min(r["control"]["max_logit_gap"] for r in rows),
        "program_all_correct": all(r["program"]["correct"] for r in rows),
        "control_none_correct": not any(r["control"]["correct"]
                                        for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
