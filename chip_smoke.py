#!/usr/bin/env python3
"""Smoke test of the DWT engine on a TPU, through its public entry points.

    python chip_smoke.py [--seed N]       # one chip: phases a-e below
    python chip_smoke.py --chips 4        # four chips: the tiled path only

One chip, one process, every phase in it:

a. device check: the first JAX device must be a TPU, else exit 1;
b. DCI 4K frame (3x2160x4096 f32, cdf97, 4 levels): ns-polyconv on
   pallas at fuse none / levels (pyramid must be refused at plan build),
   ns-polyconv on xla at levels, sep-lifting on pallas at none, and the
   5/3 ns-lifting on pallas at levels;
c. Sentinel-2 band (10980x10980 f32, 2 levels): pallas and xla at levels;
d. serve: a DwtServer with ``backend="auto"`` answers 12 DCI 2K
   (1080x2048) requests at 3 levels; every bucket must resolve to pallas;
e. counters: no degradation hop, no retry, no VMEM fallback.

Forward coefficients are checked against the jnp backend run on the
host's CPU device (rtol 2e-4, atol 2e-5), and inverse(forward(x))
against x (rtol 1e-3, atol 1e-4): the tolerances of docs/workloads.md.

``--chips 4`` runs a 16384x16384 f32 image through ``dwt2_tiled`` /
``idwt2_tiled`` with one 8192x8192 tile per device of a 2x2 mesh
(``transport="shard_map"``) and compares it with monolithic ``dwt2`` on
the first device.

The block table and profile store point into ``chiprun_out/chip_smoke/``
and start empty, so no tuning file on disk steers the run.  The last
line of standard output is one JSON object: ``{"ok": true, "device":
{"platform", "kind", "count"}}``.  Wall times are printed for
information; they include compilation on the first call.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"

DCI4K = (3, 2160, 4096)      # three components of a DCI 4K frame
SENTINEL2 = (10980, 10980)   # one 10 m Sentinel-2 band
DCI2K = (1080, 2048)         # one DCI 2K component
TILED = (16384, 8192)        # --chips 4: image edge, tile edge

PARITY = dict(rtol=2e-4, atol=2e-5)      # same transform, other path
ROUND_TRIP = dict(rtol=1e-3, atol=1e-4)  # fp32 lifting is not bitwise


class SmokeFailure(RuntimeError):
    pass


def log(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileStats:
    """Backend compilations (persistent-cache loads included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.calls, self.seconds, self.hits = 0, 0.0, 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.calls += 1
            self.seconds += secs


def isolate_selection() -> None:
    """Static block rule, empty profile store: nothing untracked on disk
    decides what runs."""
    OUT.mkdir(parents=True, exist_ok=True)
    for env, name in (("REPRO_BLOCK_TABLE", "BLOCK_TABLE.json"),
                      ("REPRO_PROFILE_STORE", "PROFILE_STORE.jsonl")):
        path = OUT / name
        path.unlink(missing_ok=True)
        os.environ[env] = str(path)


def leaves(pyr):
    return [pyr.ll] + [d for det in pyr.details for d in det]


def err_ratio(got, want, rtol: float, atol: float):
    """(max |got - want|, max of |got - want| / (atol + rtol |want|)):
    the pair passes when the ratio is at most 1."""
    import numpy as np
    worst_abs = worst_ratio = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        check(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
        d = np.abs(g - w)
        worst_abs = max(worst_abs, float(d.max()))
        worst_ratio = max(worst_ratio, float((d / (atol + rtol * np.abs(w)))
                                             .max()))
    return worst_abs, worst_ratio


def timed(fn, *args):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def reference(x_cpu, cpu, **cfg):
    """jnp-backend forward on the host's CPU device."""
    import jax
    from repro.core import dwt2
    with jax.default_device(cpu):
        return jax.block_until_ready(dwt2(x_cpu, backend="jnp",
                                          fuse="levels", **cfg))


def run_case(name, x_cpu, ref, *, backend, fuse, **cfg):
    """Forward + inverse of one configuration; parity and round trip.

    Only the buffers a caller would hold stay on the device: the image
    while it is transformed, then the pyramid while it is inverted (the
    xla backend at Sentinel-2 size takes 15.3 of the v5e's 15.75 GiB)."""
    import jax
    from repro import engine
    from repro.core import dwt2, idwt2
    x = jax.device_put(x_cpu, jax.devices()[0])
    shape = tuple(x.shape)
    fwd = lambda v: dwt2(v, backend=backend, fuse=fuse, **cfg)  # noqa: E731
    t_fwd = timed(fwd, x)[1]
    pyr, t_fwd2 = timed(fwd, x)
    del x
    rec, t_inv = timed(lambda p: idwt2(p, backend=backend, fuse=fuse,
                                       wavelet=cfg["wavelet"],
                                       scheme=cfg["scheme"]), pyr)
    plan = engine.get_plan(shape=shape, backend=backend, fuse=fuse, **cfg)
    k = plan.key
    blocks = [ls.block for ls in plan.level_specs]
    e_abs, e_fwd = err_ratio(leaves(pyr), leaves(ref), **PARITY)
    r_abs, e_rt = err_ratio([rec], [x_cpu], **ROUND_TRIP)
    log(f"[{name}] plan backend={k.backend} fuse={k.fuse} scheme={k.scheme}"
        f" wavelet={k.wavelet} levels={k.levels} shape={k.shape}"
        f" blocks={blocks} launches={plan.pallas_calls}")
    log(f"[{name}] forward max|err|={e_abs:.3e} (ratio {e_fwd:.3f} of "
        f"rtol={PARITY['rtol']}) round-trip max|err|={r_abs:.3e} "
        f"(ratio {e_rt:.3f} of rtol={ROUND_TRIP['rtol']})")
    log(f"[{name}] wall s: fwd first={t_fwd:.3f} fwd again={t_fwd2:.4f} "
        f"inv first={t_inv:.3f}")
    check((k.backend, k.fuse) == (backend, fuse),
          f"{name}: asked {backend}/{fuse}, plan ran {k.backend}/{k.fuse}")
    check(e_fwd <= 1.0, f"{name}: forward parity off by {e_fwd:.3f}x tol")
    check(e_rt <= 1.0, f"{name}: round trip off by {e_rt:.3f}x tol")


def phase_dci4k(key, cpu):
    import jax
    from repro import engine
    from repro.core import dwt2
    x_cpu = jax.device_put(jax.random.normal(key, DCI4K, "float32"), cpu)
    for wavelet, cases in (
            ("cdf97", (("ns-polyconv", "pallas", "none"),
                       ("ns-polyconv", "pallas", "levels"),
                       ("ns-polyconv", "xla", "levels"),
                       ("sep-lifting", "pallas", "none"))),
            ("cdf53", (("ns-lifting", "pallas", "levels"),))):
        ref = reference(x_cpu, cpu, wavelet=wavelet, levels=4,
                        scheme="ns-polyconv")
        for scheme, backend, fuse in cases:
            run_case(f"dci4k {wavelet} {scheme} {backend}/{fuse}", x_cpu,
                     ref, backend=backend, fuse=fuse, wavelet=wavelet,
                     levels=4, scheme=scheme)
    try:
        dwt2(jax.device_put(x_cpu, jax.devices()[0]), wavelet="cdf97",
             levels=4, scheme="ns-polyconv", backend="pallas",
             fuse="pyramid")
    except engine.BackendError as e:
        check("fuse" in str(e), f"pyramid rejection names no field: {e}")
        log(f"[dci4k cdf97 ns-polyconv pallas/pyramid] refused at plan "
            f"build: {e}")
    else:
        raise SmokeFailure("fuse='pyramid' ran on the TPU; it must be "
                           "refused at plan build")


def phase_sentinel2(key, cpu):
    import jax
    x_cpu = jax.device_put(jax.random.normal(key, SENTINEL2, "float32"),
                           cpu)
    cfg = dict(wavelet="cdf97", levels=2, scheme="ns-polyconv")
    ref = reference(x_cpu, cpu, **cfg)
    for backend in ("pallas", "xla"):
        run_case(f"sentinel2 {backend}/levels", x_cpu, ref,
                 backend=backend, fuse="levels", **cfg)


def phase_serve(key):
    import jax
    import numpy as np
    from repro import engine
    from repro.core import dwt2
    from repro.serve import BucketSpec, DwtServer, ServeConfig
    cfg = dict(wavelet="cdf97", scheme="ns-polyconv", levels=3,
               backend="auto", fuse="levels")
    imgs = np.asarray(jax.random.normal(key, (12,) + DCI2K, "float32"))
    srv = DwtServer(ServeConfig(max_batch=4, max_wait_ms=20.0))
    spec = BucketSpec(shape=DCI2K, **cfg)
    srv.warmup([spec])

    async def serve():
        async with srv:
            return await asyncio.gather(*(srv.submit(im, **cfg)
                                          for im in imgs))

    t = time.perf_counter()
    outs = asyncio.run(serve())
    t_serve = time.perf_counter() - t
    from repro.serve import bucket as BK
    resolved = set()
    for b in BK.bucket_batches(4):
        plan = engine.get_plan(**spec.key().plan_kwargs(b))
        resolved.add((b, plan.key.backend, plan.key.fuse,
                      plan.auto.source if plan.auto else None))
    log(f"[serve dci2k auto] buckets resolved to "
        f"{sorted(resolved)} blocks="
        f"{[ls.block for ls in plan.level_specs]}")
    check(all(r[1] == "pallas" for r in resolved),
          f"serve buckets did not resolve to pallas: {sorted(resolved)}")
    worst = (0.0, 0.0)
    for im, got in zip(imgs, outs):
        want = dwt2(im, **cfg)
        worst = max(worst, err_ratio(leaves(got), leaves(want), **PARITY),
                    key=lambda p: p[1])
    log(f"[serve dci2k auto] 12 requests in {t_serve:.3f} s; max|err| vs "
        f"direct dwt2={worst[0]:.3e} (ratio {worst[1]:.3f} of "
        f"rtol={PARITY['rtol']})")
    check(worst[1] <= 1.0, "served output disagrees with direct dwt2")


def phase_counters():
    from repro.engine import plan as PL
    from repro.faults import degrade as DG
    res = DG.stats()
    vmem = int(PL.VMEM_FALLBACKS.value())
    launches = int(PL.PYRAMID_LAUNCHES.value())
    log(f"[counters] fallbacks={res['fallbacks']} retries={res['retries']}"
        f" vmem_fallbacks={vmem} pyramid_launches={launches} (pyramid is "
        f"refused on the TPU)")
    check(res["fallbacks"] == 0, "a plan was served by a degradation hop")
    check(res["retries"] == 0, "an execution was retried")
    check(vmem == 0, "a pyramid plan fell back on its VMEM budget")


def phase_four_chips(key):
    import jax
    from repro.core import dwt2
    from repro.distributed.sharding import make_tile_mesh
    from repro.tiling import dwt2_tiled, idwt2_tiled
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs four devices, have {len(devs)}")
    cfg = dict(wavelet="cdf97", levels=4, scheme="ns-polyconv",
               backend="pallas", fuse="levels")
    n, tile = TILED
    x = jax.device_put(jax.random.normal(key, (n, n), "float32"), devs[0])
    mesh = make_tile_mesh(2, 2)
    tiled = dict(tiles=(tile, tile), transport="shard_map", mesh=mesh)
    pyr, t_fwd = timed(lambda v: dwt2_tiled(v, **cfg, **tiled), x)
    on = sorted(d.id for d in pyr.ll.sharding.device_set)
    log(f"[4 chips] tiled forward on devices {on}, wall s first={t_fwd:.3f}")
    check(len(on) == 4, f"tiled forward ran on devices {on}, not four")
    mono, t_mono = timed(lambda v: dwt2(v, **cfg), x)
    e_abs, e_fwd = err_ratio(leaves(pyr), leaves(mono), **PARITY)
    log(f"[4 chips] tiled vs monolithic dwt2 on device {devs[0].id}: "
        f"max|err|={e_abs:.3e} (ratio {e_fwd:.3f} of rtol={PARITY['rtol']})"
        f"; monolithic wall s first={t_mono:.3f}")
    check(e_fwd <= 1.0, f"tiled forward off by {e_fwd:.3f}x tol")
    rec, t_inv = timed(lambda p: idwt2_tiled(
        p, wavelet="cdf97", scheme="ns-polyconv", backend="pallas",
        fuse="levels", **tiled), pyr)
    r_abs, e_rt = err_ratio([rec], [x], **ROUND_TRIP)
    log(f"[4 chips] tiled inverse round-trip max|err|={r_abs:.3e} (ratio "
        f"{e_rt:.3f} of rtol={ROUND_TRIP['rtol']}), wall s first="
        f"{t_inv:.3f}")
    check(e_rt <= 1.0, f"tiled round trip off by {e_rt:.3f}x tol")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package (src/repro) is not next to "
              "this script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    isolate_selection()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (first JAX device is "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    from importlib import metadata
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    count = len(jax.devices())
    log(f"[device] kind={dev.device_kind!r} count={count} "
        f"jax={jax.__version__} jaxlib={metadata.version('jaxlib')} "
        f"libtpu={metadata.version('libtpu')} compile_cache={cache}")

    compiles = CompileStats()
    key = jax.random.PRNGKey(args.seed)
    k4, ks2, kserve, k4c = jax.random.split(key, 4)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(k4c)
        else:
            cpu = jax.devices("cpu")[0]
            phase_dci4k(k4, cpu)
            phase_sentinel2(ks2, cpu)
            phase_serve(kserve)
            phase_counters()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"{compiles.calls} backend compiles took {compiles.seconds:.1f} s, "
        f"{compiles.hits} of them loaded from the persistent cache")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
