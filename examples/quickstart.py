"""Quickstart: the paper's schemes on an image, all equal, steps halved.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro import engine as E
from repro.compile_cache import enable_compile_cache
from repro.core import dwt2, idwt2
from repro.core import schemes as S
from repro.core import optimize as O
from repro.kernels import ops as K


def make_test_image(n=256):
    yy, xx = np.mgrid[0:n, 0:n] / n
    img = (np.sin(8 * np.pi * yy) * np.cos(6 * np.pi * xx)
           + ((yy - 0.5) ** 2 + (xx - 0.5) ** 2 < 0.1))
    return jnp.asarray(img, jnp.float32)


def main():
    enable_compile_cache()
    img = make_test_image()
    print("image:", img.shape)

    print("\n-- the six schemes (paper Sections 2-4), CDF 9/7 --")
    ref = None
    for scheme in S.SCHEMES:
        sch = S.build_scheme("cdf97", scheme)
        pyr = dwt2(img, wavelet="cdf97", levels=3, scheme=scheme)
        rec = idwt2(pyr, wavelet="cdf97", scheme=scheme)
        err = float(jnp.max(jnp.abs(rec - img)))
        ll = np.asarray(pyr.ll)
        if ref is None:
            ref = ll
        dev = float(np.max(np.abs(ll - ref)))
        print(f"  {scheme:13s} steps/level={sch.num_steps}  "
              f"ops/quad={sch.num_ops:3d}  reconstruction_err={err:.2e}  "
              f"vs_ref={dev:.2e}")

    print("\n-- Section 5 optimization: fewer ops, same steps --")
    for scheme in ("ns-conv", "ns-polyconv", "ns-lifting"):
        raw = S.build_scheme("cdf97", scheme)
        opt = O.build_optimized("cdf97", scheme)
        print(f"  {scheme:13s} ops {raw.num_ops:3d} -> {opt.num_ops:3d}  "
              f"(steps {raw.num_steps} unchanged)")

    print("\n-- Pallas TPU kernels (interpret mode on CPU) --")
    y = K.apply_scheme_pallas(img, wavelet="cdf97", scheme="ns-polyconv",
                              optimize=True, block=(64, 128))
    ll, hl, lh, hh = (np.asarray(p) for p in y)
    print(f"  kernel subbands: LL{ll.shape} HL{hl.shape} "
          f"LH{lh.shape} HH{hh.shape}")
    print(f"  LL energy fraction: "
          f"{(ll**2).sum() / (np.asarray(img)**2).sum():.3f}")
    st = K.scheme_stats("cdf97", "sep-conv", False, img.shape)
    stn = K.scheme_stats("cdf97", "ns-conv", False, img.shape)
    print(f"  HBM round trips: sep-conv {st['pallas_calls']} vs "
          f"ns-conv {stn['pallas_calls']}  (bytes "
          f"{st['hbm_bytes']/1e6:.1f}MB -> {stn['hbm_bytes']/1e6:.1f}MB)")

    print("\n-- plan/executor engine: batched, multi-level, cached --")
    batch = jnp.stack([img] * 8)               # (8, 256, 256)
    pyr = dwt2(batch, wavelet="cdf97", levels=3, scheme="ns-polyconv",
               fuse="levels")                  # one traced computation
    rec = idwt2(pyr, wavelet="cdf97", scheme="ns-polyconv", fuse="levels")
    err = float(jnp.max(jnp.abs(rec - batch)))
    print(f"  batched pyramid: LL{tuple(pyr.ll.shape)}  "
          f"reconstruction_err={err:.2e}")
    dwt2(batch, wavelet="cdf97", levels=3, scheme="ns-polyconv",
         fuse="levels")                        # same key -> cache hit
    stats = E.plan_cache_stats()
    print(f"  plan cache: {stats['hits']} hits / {stats['misses']} misses "
          f"({stats['size']} plans resident)")
    plan = E.get_plan(wavelet="cdf97", scheme="ns-polyconv", levels=3,
                      shape=batch.shape, dtype="float32", backend="pallas",
                      fuse="levels")
    print(f"  pallas plan: {plan.num_steps} steps -> "
          f"{plan.pallas_calls} kernel launches per batch "
          f"(any batch size)")


if __name__ == "__main__":
    main()
