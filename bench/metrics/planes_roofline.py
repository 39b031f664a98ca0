"""The polyphase split's share of the HBM roofline, whatever implements
it: the least bytes of the split work (:func:`split_bytes`, at the peak
of ``bench/peaks.json``) over the device time per transform under the
``dwt.to_planes`` and ``dwt.level_shift`` scopes of ``jit_dwt_forward``
in the traced window."""
import math

import numpy as np

from bench import ideal_bytes, scopes

SCOPES = ("dwt.to_planes", "dwt.level_shift")


def split_bytes(shape, levels: int, in_itemsize: int,
                out_itemsize: int) -> int:
    """The image read once at its samples' itemsize and written as
    planes of the coefficients' itemsize, plus each deeper level's split:
    its LL image read and written at the coefficients' itemsize."""
    *batch, h, w = (int(n) for n in shape)
    b = math.prod(batch)
    total = b * h * w * (int(in_itemsize) + int(out_itemsize))
    for lvl in range(1, levels):
        total += b * (h >> lvl) * (w >> lvl) * 2 * int(out_itemsize)
    return total


def read(ctx):
    t = ctx.trace
    done = ctx.window.extra.get("transforms")
    io = ctx.window.extra.get("io_dtypes")
    names = scopes.module_scopes("jit_dwt_forward")
    if t is None or not done or io is None or names is None:
        return None
    by_scope = scopes.scope_seconds(t.op_s, names)
    secs = sum(by_scope.get(s, 0.0) for s in SCOPES)
    if not secs:
        return None
    c = ctx.config
    least = split_bytes(c["shape"], c["levels"],
                        *(np.dtype(d).itemsize for d in io)) / (
        ideal_bytes.peaks(ctx.devices[0].device_kind)["hbm_bytes_per_s"]
        * len(ctx.devices))
    return 100.0 * least / (secs / done)
