"""Share of the traced window in which no operation ran on the device,
averaged over the chips of the cell."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * t.idle_share
