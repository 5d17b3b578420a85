"""Share of the traced window in which no operation ran on a device,
in percent, averaged over the cell's chips."""


def compute(ctx):
    tr = ctx.trace
    if not tr.devices:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s)
