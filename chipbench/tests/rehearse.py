"""Drive one tiny cell end to end on the CPU, past the harness's look for
a chip, and print the result line as a run on the chip would:

    JAX_PLATFORMS=cpu python3 -m chipbench.tests.rehearse mistral-7b chat 1
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src")]

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def main(config: str, traffic: str, trace: int) -> None:
    import jax

    from chipbench import harness
    from chipbench.tests.tiny import tiny_cell

    harness.peaks_for = lambda kind: CPU_PEAKS
    cell = tiny_cell(config, traffic)
    res = harness.execute(cell, 4_000_000_007, 2.0, bool(trace),
                          jax.devices()[:1], T0)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]))
