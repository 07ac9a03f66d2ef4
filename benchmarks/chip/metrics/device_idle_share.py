"""device_idle_share: the share of the traced window in which no
operation runs on the device, averaged over the cell's chips (1 minus
the union of the device's operation intervals over the window)."""


def read(ctx):
    if not ctx.trace.devices or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
