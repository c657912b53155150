"""Device (TPU v5e): 1 minus the union of the device's op intervals over
the traced window, averaged over the chips the cell uses."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
