"""Host (the serving loop's own code): device idle time inside the
program's ``synera.step`` regions that ran an engine feed or prefill,
per such step, in the traced part of the window: the chip waiting on
the host while there is work.  Regions come from the trace's host plane,
on the device trace's clock."""
from chipbench.program_trace import spans_of, step_host_gap_ms


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return step_host_gap_ms(tr, spans_of(ctx["run"]))
