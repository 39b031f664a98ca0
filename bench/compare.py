"""The comparison that decides ``correct``, and what it reads.

Every check compares what the timed path itself produced with the
float64 reference (``bench/reference.py``) by one number: the largest
absolute difference over every coefficient (or sample) compared,
divided by the largest absolute reference value among them.  A float32
transform reads about 1e-6 here; the same transform computed in
bfloat16 (the control) reads about 1e-3.  The limit of each cell is in
its configuration file (``limits``), set from those two readings.
"""
from __future__ import annotations

import numpy as np


def rel_err(got, want) -> float:
    """max |got - want| / max |want| over paired arrays."""
    worst = scale = 0.0
    for g, w in zip(got, want, strict=True):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        if g.shape != w.shape:
            raise ValueError(f"shape {g.shape} != reference {w.shape}")
        worst = max(worst, float(np.max(np.abs(g - w))))
        scale = max(scale, float(np.max(np.abs(w))))
    return worst / scale if scale else float("inf")


def pyramid_leaves(pyr) -> list:
    """The subbands of an engine pyramid in the reference's order."""
    return [pyr.ll] + [band for det in pyr.details for band in det]


def describe_plan(ctx, shape) -> str:
    """The plan ``backend="auto"`` resolved to for ``shape`` in the
    coefficients' dtype: what the run logs, so a changed choice shows.
    The samples' ``bit_depth`` is an argument of the transform, not of
    the plan."""
    from repro import engine
    c = ctx.config
    kw = ctx.transform_kwargs()
    kw.pop("bit_depth", None)
    plan = engine.get_plan(shape=tuple(shape), dtype=c["dtype"],
                           levels=c["levels"], **kw)
    k = plan.key
    src = plan.auto.source if getattr(plan, "auto", None) else "asked"
    return (f"backend={k.backend} fuse={k.fuse} scheme={k.scheme} "
            f"levels={k.levels} compute={k.compute_dtype} shape={k.shape} "
            f"blocks={[ls.block for ls in plan.level_specs]} "
            f"launches={plan.pallas_calls} choice={src}")
