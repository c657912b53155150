"""Kernels (``kernels/partial_prefill``, paged): the least time the
kernel's logical problem needs at the chip's peaks (``flops.kernel_cost``:
query rows times their causal context, that context's keys and values
read once), over the kernel's measured time in the trace: every Pallas
custom call inside the step programs of the traced feeds and prefills."""
from chipbench import flops
from chipbench.tracing import is_kernel


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["counts"].traced:
        return None
    spans = tr.spans_named("bench.verify", "bench.prefill")
    spent = sum(o.t1 - o.t0 for s in spans for o in tr.ops_in(s, is_kernel))
    if spent <= 0:
        return None
    least = sum(flops.least_seconds(f, b, ctx["peak"])
                for _, _, subs in ctx["counts"].traced for f, b in subs)
    return 100.0 * least / spent
