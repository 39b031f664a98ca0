"""Wavelet image codec — the paper's home application domain.

    PYTHONPATH=src python examples/dwt_image_codec.py [--tiles EDGE]
        [--serve] [--size N]

Multi-level CDF 9/7 transform (the JPEG 2000 lossy wavelet) computed with
the paper's fastest scheme (non-separable polyconvolution), hard
thresholding of detail coefficients, inverse transform; rate/PSNR sweep.

``--tiles EDGE`` switches to the tiled pipeline: the image is written to
an ``np.memmap`` file (standing in for an image too large for device
memory) and the forward transform streams it through the device one
tile-row band at a time (``repro.tiling.stream_dwt2``) — the *encode*
side never materializes the image on the accelerator.  The
reconstruction then demonstrates the in-core tiled API
(``idwt2(..., tiles=...)``), which does hold the full pyramid on device.

``--serve`` runs the JPEG 2000-style tiled codec through the serving
runtime (``repro.serve``, docs/serving.md): the image splits into
independent 64x64 tiles — exactly the shape of concurrent codec traffic
— and every tile transform (forward and inverse) is a request to a
:class:`~repro.serve.DwtServer`, which coalesces them into batched
plan executions.  Same coefficients, same PSNR sweep; the serve
counters at the end show how many batches the tile wave collapsed into.
"""
import argparse
import os
import tempfile

import numpy as np
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import dwt2, idwt2, flatten_pyramid, unflatten_pyramid


def synthetic_photo(n=512, seed=0):
    """Smooth background + edges + texture: a stand-in for a photograph."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:n, 0:n] / n
    img = 0.6 * np.sin(3 * np.pi * yy) * np.cos(2 * np.pi * xx)
    img += (xx > 0.5) * 0.5 + (yy > 0.7) * 0.25          # edges
    img += 0.05 * rng.standard_normal((n, n))            # texture
    return jnp.asarray(img, jnp.float32)


def psnr(a, b):
    mse = float(jnp.mean((a - b) ** 2))
    peak = float(jnp.max(jnp.abs(a)))
    return 10 * np.log10(peak ** 2 / mse) if mse > 0 else np.inf


def main_tiled(n: int, tile: int, levels: int = 4) -> None:
    from repro.tiling import stream_dwt2
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "image.f32")
        disk = np.memmap(path, dtype=np.float32, mode="w+", shape=(n, n))
        disk[:] = np.asarray(synthetic_photo(n))   # "too big for device"
        disk.flush()
        img = np.memmap(path, dtype=np.float32, mode="r", shape=(n, n))
        print(f"out-of-core codec: {n}x{n} memmap ({img.nbytes / 2**20:.0f} "
              f"MiB on disk), CDF 9/7, {levels} levels, tile {tile}x{tile}")
        pyr = stream_dwt2(img, wavelet="cdf97", levels=levels,
                          scheme="ns-polyconv", tiles=(tile, tile))
        flat = flatten_pyramid(pyr)
        print(f"{'keep%':>7s} {'PSNR dB':>9s}")
        mags = np.sort(np.abs(np.asarray(flat)).ravel())
        ref = np.asarray(img)
        for keep in (0.2, 0.05):
            thresh = mags[int((1 - keep) * len(mags))]
            kept = jnp.where(jnp.abs(flat) >= thresh, flat, 0.0)
            rec = idwt2(unflatten_pyramid(kept, levels), wavelet="cdf97",
                        scheme="ns-polyconv", tiles=(tile, tile))
            print(f"{keep*100:6.1f}% {psnr(ref, rec):9.2f}")
        rec_full = idwt2(pyr, wavelet="cdf97", scheme="ns-polyconv",
                         tiles=(tile, tile))
        print(f"lossless roundtrip max err: "
              f"{float(jnp.max(jnp.abs(rec_full - ref))):.2e}")


def main_serve(n: int, tile: int = 64, levels: int = 3) -> None:
    import asyncio

    from repro.engine.pyramid import Pyramid
    from repro.serve import BucketSpec, DwtServer, ServeConfig, serve_stats
    kw = dict(wavelet="cdf97", scheme="ns-polyconv", backend="jnp",
              fuse="levels")
    img = np.asarray(synthetic_photo(n))
    tiles = [img[r:r + tile, c:c + tile]
             for r in range(0, n, tile) for c in range(0, n, tile)]
    print(f"served codec: {n}x{n} as {len(tiles)} independent "
          f"{tile}x{tile} tiles, CDF 9/7, {levels} levels, ns-polyconv; "
          f"every tile transform is a DwtServer request")

    srv = DwtServer(ServeConfig(max_batch=16, max_wait_ms=2.0))
    srv.warmup([BucketSpec(shape=(tile, tile), levels=levels,
                           wavelet=kw["wavelet"], scheme=kw["scheme"],
                           backend=kw["backend"], fuse=kw["fuse"])])

    def threshold(pyr, thresh):
        return Pyramid(
            ll=np.where(np.abs(pyr.ll) >= thresh, pyr.ll, 0.0),
            details=[tuple(np.where(np.abs(d) >= thresh, d, 0.0)
                           for d in dd) for dd in pyr.details])

    def assemble(recs):
        out = np.empty_like(img)
        per_row = n // tile
        for i, rec in enumerate(recs):
            r, c = divmod(i, per_row)
            out[r * tile:(r + 1) * tile, c * tile:(c + 1) * tile] = rec
        return out

    async def run():
        async with srv:
            pyrs = await asyncio.gather(
                *[srv.submit(t, levels=levels, **kw) for t in tiles])
            mags = np.sort(np.concatenate(
                [np.abs(np.asarray(p.ll)).ravel() for p in pyrs] +
                [np.abs(np.asarray(d)).ravel()
                 for p in pyrs for dd in p.details for d in dd]))
            print(f"{'keep%':>7s} {'PSNR dB':>9s}")
            for keep in (0.2, 0.05):
                t = mags[int((1 - keep) * len(mags))]
                recs = await asyncio.gather(
                    *[srv.submit_inverse(threshold(p, t), **kw)
                      for p in pyrs])
                rec = assemble(recs)
                print(f"{keep*100:6.1f}% {psnr(jnp.asarray(img), jnp.asarray(rec)):9.2f}")
            recs = await asyncio.gather(
                *[srv.submit_inverse(p, **kw) for p in pyrs])
            return assemble(recs)

    rec_full = asyncio.run(run())
    print(f"lossless roundtrip max err: "
          f"{float(np.max(np.abs(rec_full - img))):.2e}")
    st = serve_stats()
    print(f"serve counters: {st['served']} requests coalesced into "
          f"{st['batches']} batches (occupancy {st['mean_occupancy']:.2f}),"
          f" p50 {st['p50_ms']:.2f} ms, p99 {st['p99_ms']:.2f} ms")


def main():
    img = synthetic_photo()
    levels = 4
    print(f"image {img.shape}, CDF 9/7, {levels} levels, ns-polyconv "
          f"scheme (1 step per lifting pair)")
    pyr = dwt2(img, wavelet="cdf97", levels=levels, scheme="ns-polyconv")
    flat = flatten_pyramid(pyr)

    print(f"{'keep%':>7s} {'PSNR dB':>9s}")
    mags = np.sort(np.abs(np.asarray(flat)).ravel())
    for keep in (0.5, 0.2, 0.1, 0.05, 0.02):
        thresh = mags[int((1 - keep) * len(mags))]
        kept = jnp.where(jnp.abs(flat) >= thresh, flat, 0.0)
        rec = idwt2(unflatten_pyramid(kept, levels), wavelet="cdf97",
                    scheme="ns-polyconv")
        print(f"{keep*100:6.1f}% {psnr(img, rec):9.2f}")

    rec_full = idwt2(pyr, wavelet="cdf97", scheme="ns-polyconv")
    print(f"lossless roundtrip max err: "
          f"{float(jnp.max(jnp.abs(rec_full - img))):.2e}")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiles", type=int, default=None, metavar="EDGE",
                    help="tile edge for the out-of-core streamed pipeline")
    ap.add_argument("--serve", action="store_true",
                    help="push tile transforms through the batching "
                         "server (repro.serve)")
    ap.add_argument("--size", type=int, default=1024,
                    help="image edge for the --tiles/--serve pipelines")
    args = ap.parse_args()
    if args.tiles:
        main_tiled(args.size, args.tiles)
    elif args.serve:
        main_serve(min(args.size, 512))
    else:
        main()
