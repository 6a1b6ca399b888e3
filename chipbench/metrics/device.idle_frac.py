"""device.idle_frac: the share of the traced window in which no operation
ran on the device, 1 - busy / window, from the profiler trace (averaged
over the cell's chips)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.busy_s_per_device:
        return None
    return t.idle_frac
