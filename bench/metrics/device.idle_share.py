"""Share of the traced window in which no operation ran on the fullest
device: 1 - (union of its op intervals) / window, in %."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.busy_by_device:
        return None
    return 100.0 * (1.0 - max(t.busy_by_device.values()) / t.window_s)
