"""Device (TPU v5e, one replica a chip): the busiest chip's busy time
over the mean chip's, in the traced window (1 where the replicas share
the work evenly; at most the number of chips).  Nothing to read on one
chip."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busy = tr.busy_per_chip()
    mean = sum(busy) / len(busy)
    if len(busy) < 2 or mean <= 0:
        return None
    return max(busy) / mean
