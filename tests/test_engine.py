"""Plan/executor engine: cache semantics, batched execution, level fusion.

Covers the acceptance criteria of the engine refactor:
* plan-cache hit/miss counters (same key -> hit, new shape -> miss);
* batched (B, C, H, W) forward/inverse parity between the jnp and pallas
  backends for all six schemes;
* batched execution bit-identical to a per-image Python loop;
* fuse="levels" (single-trace multi-level chaining) equivalent to the
  unfused path at levels >= 3.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import engine as E
from repro.core import transform as T
from repro.core.schemes import SCHEMES


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_hit_miss_semantics():
    cache = E.PlanCache(maxsize=4)
    kw = dict(wavelet="cdf97", scheme="ns-polyconv", levels=2,
              dtype="float32", backend="jnp", cache=cache)
    p1 = E.get_plan(shape=(8, 32, 32), **kw)
    assert cache.stats() == {"hits": 0, "misses": 1, "size": 1, "maxsize": 4}
    p2 = E.get_plan(shape=(8, 32, 32), **kw)
    assert p2 is p1
    assert cache.stats()["hits"] == 1 and cache.stats()["misses"] == 1
    # a different shape is a different plan
    E.get_plan(shape=(4, 32, 32), **kw)
    assert cache.stats()["misses"] == 2
    # LRU eviction: maxsize 4, insert three more distinct keys
    for n in (64, 128, 256):
        E.get_plan(shape=(n, n), **kw)
    assert len(cache) == 4
    assert E.PlanKey(wavelet="cdf97", scheme="ns-polyconv", levels=2,
                     shape=(8, 32, 32), dtype="float32", backend="jnp",
                     optimize=False, fuse="none",
                     boundary="periodic") not in cache


def test_dwt2_uses_global_plan_cache():
    E.clear_plan_cache()
    x = _rand((2, 16, 16), seed=1)
    T.dwt2(x, wavelet="cdf53", levels=1)
    before = E.plan_cache_stats()
    T.dwt2(x, wavelet="cdf53", levels=1)
    after = E.plan_cache_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


def test_plan_precomputes_level_geometry():
    plan = E.get_plan(wavelet="cdf97", scheme="sep-lifting", levels=2,
                      shape=(64, 128), dtype="float32", backend="pallas",
                      fuse="scheme", cache=E.PlanCache())
    assert [ls.image_shape for ls in plan.level_specs] == \
        [(64, 128), (32, 64)]
    assert [ls.plane_shape for ls in plan.level_specs] == \
        [(32, 64), (16, 32)]
    assert plan.num_steps == 2 * 8          # sep-lifting CDF 9/7: 8 steps
    assert plan.pallas_calls == 2           # fused: one call per level
    # compound halo under fusion: the compiled program's per-axis margin
    # analysis — H-steps consume no vertical halo and vice versa, so the
    # 8 alternating halo-1 steps need 4, not the summed 8
    ls = plan.level_specs[0]
    assert ls.halo == ls.fwd_programs[0].halo == 4
    assert ls.halo <= sum(st.halo for st in ls.fwd_steps)


def test_plan_rejects_bad_configs():
    kw = dict(wavelet="cdf97", scheme="ns-polyconv", levels=1,
              shape=(16, 16), dtype="float32", cache=E.PlanCache())
    with pytest.raises(ValueError):
        E.get_plan(backend="cuda", **kw)
    with pytest.raises(ValueError):
        E.get_plan(fuse="everything", **kw)
    with pytest.raises(ValueError):
        E.get_plan(boundary="reflect", **kw)
    with pytest.raises(ValueError):
        E.get_plan(wavelet="cdf97", scheme="ns-polyconv", levels=3,
                   shape=(20, 20), dtype="float32", cache=E.PlanCache())


# ---------------------------------------------------------------------------
# Batched execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", SCHEMES)
def test_batched_parity_jnp_vs_pallas(scheme):
    """(B, C, H, W) forward/inverse on both backends agree."""
    x = _rand((2, 2, 16, 32), seed=2)
    pj = T.dwt2(x, wavelet="cdf97", levels=1, scheme=scheme)
    pp = T.dwt2(x, wavelet="cdf97", levels=1, scheme=scheme,
                backend="pallas")
    assert pj.ll.shape == pp.ll.shape == (2, 2, 8, 16)
    for a, b in zip([pj.ll, *pj.details[0]], [pp.ll, *pp.details[0]]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    for backend in ("jnp", "pallas"):
        pyr = pj if backend == "jnp" else pp
        xr = T.idwt2(pyr, wavelet="cdf97", scheme=scheme, backend=backend)
        np.testing.assert_allclose(np.asarray(xr), np.asarray(x),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_batched_bit_identical_to_per_image_loop(backend):
    x = _rand((3, 2, 16, 32), seed=3)
    pyr = T.dwt2(x, wavelet="cdf97", levels=2, scheme="ns-polyconv",
                 backend=backend)
    for i in range(3):
        for j in range(2):
            one = T.dwt2(x[i, j], wavelet="cdf97", levels=2,
                         scheme="ns-polyconv", backend=backend)
            np.testing.assert_array_equal(np.asarray(one.ll),
                                          np.asarray(pyr.ll[i, j]))
            for (hl, lh, hh), (bhl, blh, bhh) in zip(one.details,
                                                     pyr.details):
                np.testing.assert_array_equal(np.asarray(hl),
                                              np.asarray(bhl[i, j]))
                np.testing.assert_array_equal(np.asarray(lh),
                                              np.asarray(blh[i, j]))
                np.testing.assert_array_equal(np.asarray(hh),
                                              np.asarray(bhh[i, j]))


# ---------------------------------------------------------------------------
# Level fusion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_fuse_levels_matches_unfused(backend):
    """fuse="levels" (one trace, chained level kernels) == unfused path."""
    x = _rand((2, 32, 32), seed=4)
    base = T.dwt2(x, wavelet="cdf97", levels=3, scheme="ns-polyconv",
                  backend=backend)
    fused = T.dwt2(x, wavelet="cdf97", levels=3, scheme="ns-polyconv",
                   backend=backend, fuse="levels")
    # same kernels; only XLA reassociation under the single trace differs
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fused.ll), np.asarray(base.ll),
                               **tol)
    for (a1, a2, a3), (b1, b2, b3) in zip(fused.details, base.details):
        for a, b in zip((a1, a2, a3), (b1, b2, b3)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
    xr = T.idwt2(fused, wavelet="cdf97", scheme="ns-polyconv",
                 backend=backend, fuse="levels")
    np.testing.assert_allclose(np.asarray(xr), np.asarray(x),
                               rtol=1e-3, atol=1e-4)


def test_nonsmooth_plane_dims_use_wide_blocks():
    """Prime plane dims must not fall off the 1-wide-block cliff."""
    from repro.kernels.polyphase import _pick_block
    b, npad = _pick_block(37, 16)       # prime: pad, keep target block
    assert b == 16 and npad == 48
    b, npad = _pick_block(32, 16)       # exact divisor: no padding
    assert b == 16 and npad == 32
    # numerics through the padded path (74x106 -> 37x53 planes, both prime)
    from repro.kernels import ops as K
    from repro.kernels import ref as R
    x = _rand((74, 106), seed=5)
    oracle = R.dwt2_ref(x, "cdf97")
    y = K.apply_scheme_pallas(x, wavelet="cdf97", scheme="ns-polyconv",
                              block=(16, 32))
    for a, b in zip(oracle, y):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("fuse", ("none", "levels"))
def test_tpu_tiled_windows_match_reference(monkeypatch, fuse):
    """The TPU block/window rule, run in the interpreter: blocks on the
    (8, 128) tile, windows rounded up to whole tiles, planes padded
    periodically to cover them.  Numerics must not notice."""
    from repro.kernels import polyphase as PP
    monkeypatch.setattr(PP, "tile", lambda dtype, interpret:
                        (PP.sublanes(dtype), PP.LANES))
    x = _rand((2, 76, 532), seed=8)       # 38x266 planes, both non-smooth
    key = E.PlanKey("cdf97", "ns-polyconv", 2, x.shape, "float32",
                    "pallas", False, fuse, "periodic")
    plan = E.build_plan(key, block_target=(16, 128))
    assert plan.level_specs[0].block == (16, 128)
    assert plan.level_specs[0].padded_shape == (48, 384)
    pyr = plan.execute(x)
    ref = T.dwt2(x, wavelet="cdf97", levels=2, scheme="ns-polyconv")
    for a, b in zip([pyr.ll] + [d for det in pyr.details for d in det],
                    [ref.ll] + [d for det in ref.details for d in det]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(plan.execute_inverse(pyr)),
                               np.asarray(x), rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# LRU eviction + stats() accuracy under a mixed key population
# ---------------------------------------------------------------------------

def test_lru_eviction_and_counters_mixed_population(tmp_path, monkeypatch):
    """Tiled + pyramid + auto plans in one small cache: the LRU must
    evict the oldest key, counters must stay exact, and the stats()
    plan rows must carry the population's per-kind annotations."""
    from repro import profiler as PF
    monkeypatch.setenv(PF.STORE_ENV, str(tmp_path / "store.jsonl"))
    cache = E.PlanCache(maxsize=3)
    kw = dict(wavelet="cdf97", scheme="ns-polyconv", levels=2,
              dtype="float32", cache=cache)
    tiled = E.get_plan(shape=(64, 64), backend="pallas", fuse="none",
                       tiles=(32, 32), **kw)
    pyram = E.get_plan(shape=(2, 32, 32), backend="pallas",
                       fuse="pyramid", **kw)
    auto = E.get_plan(shape=(2, 32, 32), backend="auto", **kw)
    assert cache.stats() == {"hits": 0, "misses": 3, "size": 3,
                             "maxsize": 3}
    assert tiled.grid is not None
    assert pyram.pyramid is not None or pyram.fallback is not None
    assert auto.auto is not None and auto.key.backend != "auto"
    # re-fetches are hits for every kind, including auto (cached under
    # the backend="auto" key, no re-resolution)
    for shape, backend, extra in (((64, 64), "pallas",
                                   {"fuse": "none", "tiles": (32, 32)}),
                                  ((2, 32, 32), "pallas",
                                   {"fuse": "pyramid"}),
                                  ((2, 32, 32), "auto", {})):
        E.get_plan(shape=shape, backend=backend, **extra, **kw)
    assert cache.stats() == {"hits": 3, "misses": 3, "size": 3,
                             "maxsize": 3}
    # a fourth distinct key evicts the LRU entry (the tiled plan, which
    # was fetched least recently... the re-fetch order above makes the
    # tiled key oldest-but-refreshed; the true LRU is itself)
    E.get_plan(shape=(2, 64, 64), backend="jnp", fuse="none", **kw)
    assert cache.stats()["size"] == 3 and cache.stats()["misses"] == 4
    # the evicted key is the least-recently-used: the tiled plan was
    # refreshed first of the three, so it is evicted first
    assert E.PlanKey(wavelet="cdf97", scheme="ns-polyconv", levels=2,
                     shape=(64, 64), dtype="float32", backend="pallas",
                     optimize=False, fuse="none", boundary="periodic",
                     tiles=(32, 32)) not in cache
    # rebuilding the evicted key is a miss, and counters stay exact
    E.get_plan(shape=(64, 64), backend="pallas", fuse="none",
               tiles=(32, 32), **kw)
    assert cache.stats()["misses"] == 5 and cache.stats()["hits"] == 3


def test_stats_rows_annotate_mixed_population(tmp_path, monkeypatch):
    """stats() reads the *global* cache: seed it with the mixed
    population and assert one correctly-annotated row per plan kind."""
    from repro import profiler as PF
    monkeypatch.setenv(PF.STORE_ENV, str(tmp_path / "store.jsonl"))
    E.clear_plan_cache()
    try:
        kw = dict(wavelet="cdf97", scheme="ns-polyconv", levels=2,
                  dtype="float32")
        E.get_plan(shape=(64, 64), backend="pallas", fuse="none",
                   tiles=(32, 32), **kw)
        E.get_plan(shape=(2, 32, 32), backend="pallas", fuse="pyramid",
                   **kw)
        E.get_plan(shape=(2, 32, 32), backend="auto", **kw)
        s = E.stats()
        assert s["plan_cache"]["size"] == 3
        assert s["plan_cache"]["misses"] == 3
        tiled_rows = [r for r in s["plans"] if "tiles" in r]
        pyr_rows = [r for r in s["plans"]
                    if "pyramid_window" in r or "fallback" in r]
        auto_rows = [r for r in s["plans"] if "auto" in r]
        assert len(tiled_rows) == 1 and tiled_rows[0]["tile_count"] == 4
        assert len(pyr_rows) >= 1
        assert len(auto_rows) == 1
        auto = auto_rows[0]["auto"]
        assert auto["backend"] != "auto"
        assert auto["source"] in ("store", "model", "heuristic")
    finally:
        E.clear_plan_cache()


def test_evicted_auto_plan_reresolves_through_cost_model(tmp_path,
                                                        monkeypatch):
    """After LRU eviction an auto plan is *re-resolved*, not recalled:
    if the store learned new measurements in between, the rebuilt plan
    follows them (and the resolution counters tick again)."""
    import dataclasses
    from repro import profiler as PF
    from repro.profiler import auto as PA
    from repro.profiler.store import record_from_key

    store = PF.TraceStore(tmp_path / "store.jsonl")
    monkeypatch.setenv(PF.STORE_ENV, str(store.path))
    key = E.PlanKey(wavelet="cdf97", scheme="ns-polyconv", levels=2,
                    shape=(2, 32, 32), dtype="float32", backend="auto",
                    optimize=False, fuse="none", boundary="periodic")

    def rec(backend, fuse, t):
        concrete = dataclasses.replace(key, backend=backend, fuse=fuse,
                                       tap_opt="full")
        feats = PF.config_features(concrete)
        return record_from_key(concrete, None, t, feats["hbm_bytes"],
                               feats["launches"])

    store.extend([rec("jnp", "levels", 1e-3), rec("xla", "levels", 5e-3)])
    cache = E.PlanCache(maxsize=1)
    before = dict(PA.AUTO_COUNTERS)
    kw = dict(wavelet="cdf97", scheme="ns-polyconv", levels=2,
              dtype="float32", cache=cache)
    p1 = E.get_plan(shape=(2, 32, 32), backend="auto", **kw)
    assert (p1.key.backend, p1.auto.source) == ("jnp", "store")
    # evict the auto plan, then teach the store a faster config
    E.get_plan(shape=(2, 64, 64), backend="jnp", fuse="none", **kw)
    assert len(cache) == 1
    store.append(rec("xla", "levels", 1e-5))
    p2 = E.get_plan(shape=(2, 32, 32), backend="auto", **kw)
    assert p2 is not p1
    assert (p2.key.backend, p2.key.fuse) == ("xla", "levels")
    assert p2.auto.source == "store"
    assert PA.AUTO_COUNTERS["store_hits"] == before["store_hits"] + 2
    assert cache.stats()["misses"] == 3 and cache.stats()["hits"] == 0
