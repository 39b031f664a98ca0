"""Offline decoder: ``idwt2`` in a closed loop over a ring of coefficient
pyramids that are already on the device (drawn from the seed like
images), at most ``in_flight`` outstanding.  Traffic keys as for
``encode``."""
from __future__ import annotations

import math

import numpy as np

from bench import closed_loop, compare, data, reference
from bench.harness import BenchError, Check, Window

SPAN = "bench.decode"


def setup(ctx):
    import jax
    from repro.core import idwt2
    from repro.engine.pyramid import Pyramid
    c = ctx.config
    if data.integer_samples(c):
        raise BenchError(f"the decode driver takes float samples; "
                         f"{c['name']!r} states {c['samples']!r} samples")
    lv = c["levels"]
    ring = [Pyramid(ll=p[0], details=[tuple(p[1 + 3 * i:4 + 3 * i])
                                      for i in range(lv)])
            for p in data.pyramids(ctx.seed, ctx.traffic["ring"],
                                   c["shape"], lv, c["bit_depth"])]
    kw = ctx.transform_kwargs()

    def call(p):
        return idwt2(p, **kw)

    for _ in range(2):
        jax.block_until_ready(call(ring[0]))
    ctx.log(f"[plan] {compare.describe_plan(ctx, c['shape'])}")
    return {"call": call, "ring": ring}


def run(ctx, st):
    t = ctx.traffic
    done, secs, dispatch, kept = closed_loop.run(
        ctx, st["call"], st["ring"], in_flight=t["in_flight"],
        keep=t["keep"], rng=data.host_rng(ctx.seed), span=SPAN)
    st["kept"] = kept
    mpix = math.prod(ctx.config["shape"]) / 1e6
    ctx.log(f"[loop] {done} inverses of {mpix:.3f} Mpix in {secs:.3f} s")
    return Window(seconds=secs, attempted=done, failed=0,
                  metrics={"decode_mpix_s": done * mpix / secs},
                  extra={"dispatch_s": dispatch, "transforms": done,
                         "io_dtypes": (ctx.config["dtype"],
                                       data.samples_dtype(ctx.config))})


def check(ctx, st):
    c = ctx.config
    kept = [(idx, np.asarray(out)) for idx, out in st.pop("kept")]
    pyrs = {idx: [np.asarray(a) for a in compare.pyramid_leaves(
        st["ring"][idx])] for idx, _ in kept}
    st.clear()
    worst = 0.0
    for idx, got in kept:
        leaves = pyrs[idx]
        det = [tuple(leaves[1 + 3 * i:4 + 3 * i])
               for i in range(c["levels"])]
        want = reference.idwt2(leaves[0], det, c["wavelet"])
        worst = max(worst, compare.rel_err([got], [want]))
    ctx.log(f"[check] {len(kept)} kept images against the reference")
    return [Check("rel_err", worst, c["limits"]["rel_err"])]
