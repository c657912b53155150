"""Scheduler (``serving/scheduler.py``): median time a verify request
waited before the iteration that first fed it began, over the verify
requests due in the window: ``dispatch_ms - arrival_ms`` from the
program's own queue records (``Tracer.queue``), on the run's clock."""
import numpy as np

from chipbench.program_trace import queue_waits


def read(ctx):
    waits = queue_waits(ctx["run"])
    if not waits:
        return None
    return float(np.median(waits))
