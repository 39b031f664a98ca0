"""Offline encoder: ``dwt2`` in a closed loop over a ring of frames that
are already on the device, at most ``in_flight`` transforms
outstanding.  Traffic keys: ``ring``, ``in_flight``, ``keep`` (results
kept for the output check).  Frames are the configuration's samples
(``bench/data.py``): integer ones go to ``dwt2`` with their
``bit_depth`` and are level-shifted for the reference."""
from __future__ import annotations

import math

import numpy as np

from bench import closed_loop, compare, data, reference
from bench.harness import Check, Window

SPAN = "bench.encode"


def setup(ctx):
    import jax
    from repro.core import dwt2
    c = ctx.config
    ring = data.images(ctx.seed, ctx.traffic["ring"], c["shape"],
                       c["bit_depth"], data.samples_dtype(c))
    kw = dict(ctx.transform_kwargs(), levels=c["levels"])

    def call(x):
        return dwt2(x, **kw)

    for _ in range(2):              # the first compiles or loads the plan
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
    ctx.log(f"[loop] {done} transforms of {mpix:.3f} Mpix in {secs:.3f} s")
    return Window(seconds=secs, attempted=done, failed=0,
                  metrics={"encode_mpix_s": done * mpix / secs},
                  extra={"dispatch_s": dispatch, "transforms": done,
                         "io_dtypes": (data.samples_dtype(ctx.config),
                                       ctx.config["dtype"])})


def reference_input(frame, config: dict) -> np.ndarray:
    """A frame as the transform sees it, in float64: integer samples
    DC-level-shifted by ``2**(bit_depth - 1)`` (ISO/IEC 15444-1 Annex
    G.1.2), float samples (already shifted) as they are."""
    x = np.array(frame, np.float64)
    if data.integer_samples(config):
        x -= 2.0 ** (config["bit_depth"] - 1)
    return x


def check(ctx, st):
    """Host copies of the kept pyramids and their frames, then the
    device state is dropped, then the reference runs."""
    c = ctx.config
    kept = [(idx, [np.asarray(a) for a in compare.pyramid_leaves(out)])
            for idx, out in st.pop("kept")]
    frames = {idx: np.asarray(st["ring"][idx]) for idx, _ in kept}
    st.clear()
    worst = 0.0
    for idx, got in kept:
        ll, det = reference.dwt2(reference_input(frames[idx], c),
                                 c["wavelet"], c["levels"])
        worst = max(worst, compare.rel_err(got, reference.leaves(ll, det)))
    ctx.log(f"[check] {len(kept)} kept pyramids against the reference")
    return [Check("rel_err", worst, c["limits"]["rel_err"])]
