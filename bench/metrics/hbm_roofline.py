"""The whole transform's share of the HBM roofline: the least time the
cell's chips need to read the input once and write the output once
(``bench/ideal_bytes.py``, at the peak of ``bench/peaks.json``) over
the device busy time per transform in the traced window.  The input and
the output count at their own itemsizes, as the driver reports them
(``io_dtypes``: samples in and coefficients out for the forward, the
other way round for the inverse).  The bytes bound this transform, not
the arithmetic (see PERF.md)."""
import numpy as np

from bench import ideal_bytes


def read(ctx):
    t = ctx.trace
    done = ctx.window.extra.get("transforms")
    io = ctx.window.extra.get("io_dtypes")
    if t is None or not t.busy_s or not done or io is None:
        return None
    least = ideal_bytes.least_seconds(ctx.config["shape"],
                                      *(np.dtype(d).itemsize for d in io),
                                      ctx.devices[0].device_kind,
                                      len(ctx.devices))
    return 100.0 * least / (t.busy_s / done)
