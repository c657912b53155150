"""A tiny fleet cell end to end on the CPU with the timed path broken on
one replica alone, and the result line printed:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 -m chipbench.tests.rehearse_fault half_batch 3

The fault (``test_chipbench_faults.FAULTS``) wraps the step programs of
the engine built for that replica; the others serve as they should.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src")]


def main(fault: str, replica: int, chips: int = 4) -> None:
    import jax

    import repro.serving.engine as engine_mod
    from chipbench import harness
    from chipbench.tests.rehearse import CPU_PEAKS
    from chipbench.tests.test_chipbench_faults import FAULTS
    from chipbench.tests.tiny import tiny_cell

    cell = tiny_cell("qwen2-7b", "documents", chips)
    vocab = cell["model"]["vocab_size"]
    make, init = (engine_mod.make_cloud_verify_step,
                  engine_mod.CloudEngine.__init__)
    built = []                       # engines whose set-up has begun

    def counted_init(self, *a, **kw):
        built.append(self)
        init(self, *a, **kw)

    def broken(cfg, **kw):
        step = make(cfg, **kw)
        return FAULTS[fault](step, vocab) if len(built) - 1 == replica \
            else step

    engine_mod.CloudEngine.__init__ = counted_init
    engine_mod.make_cloud_verify_step = broken
    harness.peaks_for = lambda kind: CPU_PEAKS
    res = harness.execute(cell, 77_000_000_003, 2.0, False,
                          jax.devices()[:chips], T0)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
