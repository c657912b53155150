"""Run one cell of the benchmark on the chips of this machine.

    python3 chipbench/run.py --workload mistral-7b.chat --seed 7 \
        --seconds 51 --trace 0

Prints earlier ``chipbench <tag> {json}`` lines, the compared numbers
beside their limits as the last lines of standard error, and as the
last line of standard output one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``.  It refuses to run, and prints no result, where JAX finds
no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def use_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every
    program cached, so only a checkout's first run compiles."""
    from repro.launch.compile_cache import use_compile_cache

    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    from chipbench import cells, harness

    cell = cells.cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU found (JAX sees {devices[0].platform}); "
              "refusing to measure", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    harness.peaks_for(devices[0].device_kind)
    cache = use_cache(jax)
    harness.log("device", **harness.device_info(devices), jax=jax.__version__,
                compile_cache=cache, workload=args.workload, seed=args.seed,
                seconds=args.seconds, trace=args.trace)
    result = harness.execute(cell, args.seed, args.seconds, bool(args.trace),
                             devices[:cell["chips"]], T_PROCESS0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
