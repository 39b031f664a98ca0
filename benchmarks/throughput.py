"""Benchmark: paper Figures 7/8/9 — transform performance per scheme,
plus the plan/executor engine's batched-throughput comparison.

The paper measures GB/s versus image size on two GPUs.  The analogue
here is wall-clock GB/s of the jitted pure-JAX scheme implementations
on the CPU (relative scheme ordering under a real memory hierarchy).
Device numbers come from the chip benchmark (``bench/``), not from
here.

``engine_throughput`` measures the production question instead: batched
images/sec through the plan-cached engine (one cached plan, one traced
computation per batch) versus seed-style dispatch (scheme algebra rebuilt
on every call, one Python-level call per image) — wall clock, not op
counts.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import engine as E
from repro.core import schemes as S
from repro.core import transform as T


def measure_cpu(wname: str, scheme: str, n: int, reps: int = 3) -> float:
    """GB/s processed by the full 2-D transform on an n x n image."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.float32)

    @jax.jit
    def f(x):
        return S.forward(x, wname, scheme)

    jax.block_until_ready(f(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(x))
    dt = (time.perf_counter() - t0) / reps
    return x.nbytes / dt / 1e9


def _seed_style_dwt2(x, wavelet: str, scheme: str, levels: int):
    """The pre-engine hot path, reproduced for comparison: the scheme
    algebra (pure-Python Laurent-polynomial products) is rebuilt on every
    level of every call, and application is eager per-image jnp."""
    ll = x
    details = []
    for _ in range(levels):
        sch = S.build_scheme(wavelet, scheme)
        planes = S.apply_scheme(sch, S.to_planes(ll))
        ll = planes[0]
        details.append(planes[1:])
    return ll, details


def _time(fn, reps: int) -> float:
    jax.block_until_ready(fn())  # warm caches / compiles, drain dispatch
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def engine_throughput(batch_sizes=(1, 8, 32), n: int = 128,
                      levels: int = 2, wavelet: str = "cdf97",
                      scheme: str = "ns-polyconv", reps: int = 5,
                      pallas_n: int = 64, pallas_batch: int = 8,
                      backends=None):
    """Plan-cached batched engine vs seed-style per-call dispatch, over
    every registered backend (or the ``backends`` subset)."""
    if backends is None:
        backends = E.available_backends()
    print("# engine: batched images/sec, plan-cached vs seed-style "
          f"dispatch ({wavelet}/{scheme}, {levels} levels, "
          f"backends {tuple(backends)})")
    print("backend,batch,size,seed_img_per_s,engine_img_per_s,speedup")
    rng = np.random.default_rng(0)
    rows = []
    speedups = {}
    if "jnp" in backends:
        for b in batch_sizes:
            x = jnp.asarray(rng.standard_normal((b, n, n)), jnp.float32)
            t_seed = _time(
                lambda: [_seed_style_dwt2(x[i], wavelet, scheme, levels)
                         for i in range(b)], reps)
            t_eng = _time(
                lambda: T.dwt2(x, wavelet=wavelet, levels=levels,
                               scheme=scheme, fuse="levels"), reps)
            rows.append({"backend": "jnp", "batch": b, "size": n,
                         "seed_img_per_s": b / t_seed,
                         "engine_img_per_s": b / t_eng})
            speedups["jnp"] = t_seed / t_eng
            print(f"jnp,{b},{n},{b / t_seed:.1f},{b / t_eng:.1f},"
                  f"{t_seed / t_eng:.2f}x")

    # kernel backends: batched execution (batch on the leading grid dim /
    # conv N dim) vs a per-image loop of jitted single-image calls (seed
    # granularity).  pallas runs the interpreter on CPU, hence the label.
    for bk in backends:
        if bk == "jnp":
            continue
        b, m = pallas_batch, pallas_n
        x = jnp.asarray(rng.standard_normal((b, m, m)), jnp.float32)
        t_loop = _time(
            lambda: [T.dwt2(x[i], wavelet=wavelet, levels=levels,
                            scheme=scheme, backend=bk) for i in range(b)],
            reps)
        t_eng = _time(
            lambda: T.dwt2(x, wavelet=wavelet, levels=levels, scheme=scheme,
                           backend=bk, fuse="levels"), reps)
        label = "pallas-interpret" if bk == "pallas" else bk
        rows.append({"backend": label, "batch": b, "size": m,
                     "seed_img_per_s": b / t_loop,
                     "engine_img_per_s": b / t_eng})
        speedups[label] = t_loop / t_eng
        print(f"{label},{b},{m},{b / t_loop:.1f},{b / t_eng:.1f},"
              f"{t_loop / t_eng:.2f}x")
    print(f"# plan cache: {E.plan_cache_stats()}")
    # "speedup" keeps its historical meaning — the pallas batched-vs-loop
    # ratio the BENCH_*.json trend tracks — and is None when pallas was
    # not measured; per-backend ratios live in "speedups"
    return {"speedup": speedups.get("pallas-interpret"),
            "speedups": speedups, "rows": rows}


def tiled_throughput(n: int = 512, levels: int = 3, tile: int = 128,
                     wavelet: str = "cdf97", scheme: str = "ns-polyconv",
                     reps: int = 3):
    """Tiled vs monolithic wall clock, plus the streaming executor:
    images/sec through ``dwt2(..., tiles=...)`` and ``stream_dwt2`` on an
    n x n image (CPU numbers; on device the tiled path is what unlocks
    planes past single-kernel/single-device limits)."""
    from repro.tiling import stream_dwt2
    print(f"# tiling: {n}x{n}, {levels} levels, tile {tile}x{tile} "
          f"({wavelet}/{scheme})")
    print("path,img_per_s")
    rng = np.random.default_rng(0)
    xh = rng.standard_normal((n, n)).astype(np.float32)
    x = jnp.asarray(xh)
    rows = []
    t_mono = _time(lambda: T.dwt2(x, wavelet=wavelet, levels=levels,
                                  scheme=scheme, fuse="levels"), reps)
    t_tile = _time(lambda: T.dwt2(x, wavelet=wavelet, levels=levels,
                                  scheme=scheme, fuse="levels",
                                  tiles=(tile, tile)), reps)
    t_stream = _time(lambda: stream_dwt2(xh, wavelet=wavelet, levels=levels,
                                         scheme=scheme,
                                         tiles=(tile, tile)), reps)
    for path, t in (("monolithic", t_mono), ("tiled-gather", t_tile),
                    ("streaming", t_stream)):
        rows.append({"path": path, "img_per_s": 1.0 / t})
        print(f"{path},{1.0 / t:.2f}")
    return rows


def pyramid_throughput(n: int = 64, levels: int = 2, batch: int = 4,
                       wavelet: str = "cdf97", scheme: str = "ns-polyconv",
                       reps: int = 3):
    """Measured pallas (interpret on CPU) wall clock of the fused-pyramid
    megakernel versus per-level kernels, plus the engine's pyramid
    counters.  On CPU the interpreter dominates, so the interesting
    number on this host is the HBM model ratio (see the fuse-mode HBM
    section); the measured rows make regressions visible anyway."""
    print(f"# fused pyramid: pallas-interpret, batch={batch}, {n}x{n}, "
          f"{levels} levels ({wavelet}/{scheme})")
    print("fuse,img_per_s,pallas_calls")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, n, n)), jnp.float32)
    rows = []
    for fuse in ("levels", "pyramid"):
        t = _time(lambda: T.dwt2(x, wavelet=wavelet, levels=levels,
                                 scheme=scheme, backend="pallas",
                                 fuse=fuse), reps)
        plan = E.get_plan(wavelet=wavelet, scheme=scheme, levels=levels,
                          shape=x.shape, dtype="float32", backend="pallas",
                          fuse=fuse)
        rows.append({"fuse": fuse, "img_per_s": batch / t,
                     "pallas_calls": plan.pallas_calls})
        print(f"{fuse},{batch / t:.1f},{plan.pallas_calls}")
    counters = E.stats()["pyramid"]
    print(f"# pyramid counters: {counters}")
    return {"rows": rows, "counters": counters}


def packet_throughput(n: int = 128, depth: int = 2, batch: int = 4,
                      wavelet: str = "cdf97", scheme: str = "ns-polyconv",
                      reps: int = 3):
    """Wavelet-packet workloads through the plan cache: the plain
    pyramid re-expressed as a packet tree (same work as ``dwt2`` — the
    packet executor's overhead must be noise), the full depth-D tree
    (4^D leaves: the worst-case node count), and a best-basis tree
    chosen on the first image.  img/s is per batch image, forward
    transform only."""
    print(f"# packets: batch={batch}, {n}x{n}, depth {depth} "
          f"({wavelet}/{scheme}, fuse='levels')")
    print("packet,leaves,img_per_s")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, n, n)), jnp.float32)
    bb = T.best_basis(x[0], wavelet=wavelet, depth=depth, scheme=scheme)
    rows = []
    for label, spec in ((f"dwt:{depth}", f"dwt:{depth}"),
                        (f"full:{depth}", f"full:{depth}"),
                        ("best-basis", bb)):
        t = _time(lambda: T.wpt2(x, wavelet=wavelet, packet=spec,
                                 scheme=scheme, fuse="levels"), reps)
        leaves = len(T.wpt2(x[:1], wavelet=wavelet, packet=spec,
                            scheme=scheme).paths)
        rows.append({"packet": label, "leaves": leaves,
                     "img_per_s": batch / t})
        print(f"{label},{leaves},{batch / t:.1f}")
    return {"rows": rows, "best_basis_leaves": list(bb.leaves)}


def dwt3_throughput(n: int = 64, t_frames: int = 8, levels: int = 2,
                    batch: int = 2, wavelet: str = "cdf97",
                    scheme: str = "ns-polyconv", reps: int = 3,
                    backends=("jnp", "xla")):
    """3-D (t+2D) volumes through the plan cache versus the
    frame-by-frame 2-D baseline (what a caller without 3-D support
    would run: ``dwt2`` on every frame, no temporal decorrelation).
    vol/s counts whole (T, H, W) volumes."""
    print(f"# dwt3: batch={batch}, T={t_frames}, {n}x{n}, "
          f"{levels} levels ({wavelet}/{scheme}, fuse='levels')")
    print("backend,vol_per_s,frames2d_vol_per_s,ratio")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, t_frames, n, n)),
                    jnp.float32)
    rows = []
    for bk in backends:
        t3 = _time(lambda: T.dwt3(x, wavelet=wavelet, levels=levels,
                                  scheme=scheme, backend=bk,
                                  fuse="levels"), reps)
        # per-frame 2-D baseline: T frames ride the leading batch dims,
        # so this is the same conv work minus the temporal lifting
        t2 = _time(lambda: T.dwt2(x, wavelet=wavelet, levels=levels,
                                  scheme=scheme, backend=bk,
                                  fuse="levels"), reps)
        rows.append({"backend": bk, "vol_per_s": batch / t3,
                     "frames2d_vol_per_s": batch / t2})
        print(f"{bk},{batch / t3:.1f},{batch / t2:.1f},{t2 / t3:.2f}x")
    return {"rows": rows}


def main(sizes=(512, 1024, 2048), wavelets=("cdf53", "cdf97", "dd137")):
    print("# Figures 7/8/9 analogue: GB/s per scheme vs image size")
    print("wavelet,scheme,size,cpu_measured_GBps,steps")
    rows = []
    for wname in wavelets:
        for sc in S.SCHEMES:
            steps = S.build_scheme(wname, sc).num_steps
            for n in sizes:
                cpu = measure_cpu(wname, sc, n)
                rows.append({"wavelet": wname, "scheme": sc, "size": n,
                             "cpu_gbps": cpu, "steps": steps})
                print(f"{wname},{sc},{n},{cpu:.2f},{steps}")
    return rows


if __name__ == "__main__":
    main()
