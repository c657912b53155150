"""``rehearse.py`` with the program's tracer attached
(``chipbench/traced_run.py``): one tiny cell end to end on the CPU.  To
delete with ``traced_run.py``.

    JAX_PLATFORMS=cpu python3 -m chipbench.tests.rehearse_traced \\
        mistral-7b chat 1
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src")]


def main(config: str, traffic: str, trace: int) -> None:
    import jax

    from chipbench import harness, traced_run
    from chipbench.tests.rehearse import CPU_PEAKS
    from chipbench.tests.tiny import tiny_cell

    harness.peaks_for = lambda kind: CPU_PEAKS
    harness.Run = traced_run.TracedRun
    harness.TRACE_DIR = harness.TRACE_DIR / "rehearse_traced"
    res = harness.execute(tiny_cell(config, traffic), 4_000_000_011, 2.0,
                          bool(trace), jax.devices()[:1], T0)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]))
