"""Model step (``models/steps.py`` ``cloud_verify``): device time per
verify call, from the trace: the ``jit_cloud_verify`` programs that ran
inside each ``engine.feed`` host span (prefill runs the same program
inside ``engine.prefill``, which the span tells apart)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    spans = tr.spans_named("bench.verify")
    if not spans:
        return None
    dev = sum(m.t1 - m.t0 for s in spans
              for m in tr.modules_in(s, "jit_cloud_verify"))
    if dev <= 0:
        return None
    return 1e3 * dev / len(spans)
