"""Device time in the Pallas kernels over device busy time, in the
traced window; the rest is XLA glue (``to_planes``, ``from_planes``,
pads, copies)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.busy_s:
        return None
    return 100.0 * t.kernel_s / t.busy_s
