"""Scheduler (``serving/scheduler.py``): share of the rows the verify and
prefill steps computed that carried no token, in the window.  Rows
computed are the engine's padded feed (every slot, each sub-chunk at its
bucket width); rows that carry a token have a position >= 0."""


def read(ctx):
    c = ctx["counts"]
    if c.rows_computed <= 0:
        return None
    return 100.0 * (1.0 - c.rows_valid / c.rows_computed)
