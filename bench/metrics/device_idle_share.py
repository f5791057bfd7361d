"""Device: share of the traced slice in which no operation ran on the chip
(1 - busy / window, busy = union of the device's op intervals), in %."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
