"""Model step: the operations the prefill and verify steps needed for
the rows they fed (``chipbench/flops.py``: matmuls and attention over
each row's real context, never padding), over their device time in the
trace times one chip's peak.  In a fleet the device time is summed over
the chips, each call's on its own chip: the share of N chips' peak over
the time they ran step programs."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["counts"].traced:
        return None
    spans = tr.spans_named("bench.verify", "bench.prefill")
    dev = sum(m.t1 - m.t0 for s in spans
              for m in tr.modules_in(s, "jit_cloud_verify"))
    if dev <= 0:
        return None
    need = sum(f for _, f, _ in ctx["counts"].traced)
    return 100.0 * need / (dev * ctx["peak"]["bf16_flops_per_s"])
