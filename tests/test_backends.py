"""Backend registry + XLA grouped-conv executor tests.

Covers the PR-5 tentpole: the registry is the single dispatch point
(capabilities, plan-compatibility checks with actionable errors at plan
build) and ``backend="xla"`` — compiled tap programs lowered to grouped
``lax.conv_general_dilated`` calls — matches the jnp reference to fp
tolerance across every scheme, tap_opt level, pyramid depth, batch
shape and odd/prime plane size.
"""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro import engine as E
from repro import compiler as C
from repro.compiler import conv as CV
from repro.compiler import execute as CX
from repro.core import dwt2, idwt2
from repro.core.schemes import SCHEMES
from repro.engine import backends as B

WAVELET = "cdf97"


def _rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape).astype(dtype))


def _assert_pyramids_close(a, b, rtol=2e-4, atol=2e-5):
    np.testing.assert_allclose(np.asarray(a.ll), np.asarray(b.ll),
                               rtol=rtol, atol=atol)
    for da, db in zip(a.details, b.details):
        for x, y in zip(da, db):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_builtin_backends_registered():
    assert set(B.available_backends()) >= {"jnp", "pallas", "xla"}
    for name in ("jnp", "pallas", "xla"):
        bk = B.get_backend(name)
        assert bk.name == name
        caps = bk.capabilities()
        assert caps["backend"] == name and caps["fuse_modes"]


def test_unknown_backend_fails_at_plan_build_with_names():
    with pytest.raises(B.BackendError,
                       match=r"unknown backend 'cuda'.*registered "
                             r"backends.*jnp.*pallas.*xla"):
        E.get_plan(shape=(16, 16), backend="cuda", cache=E.PlanCache())
    # BackendError is a ValueError: pre-registry callers keep working
    assert issubclass(B.BackendError, ValueError)


def test_backend_rejects_plan_key_naming_field():
    # xla has no fused-pyramid megakernel: reject at plan build, naming
    # the offending PlanKey field and the supported values
    with pytest.raises(B.BackendError,
                       match=r"'xla'.*PlanKey\.fuse='pyramid'.*"
                             r"\('none', 'scheme', 'levels'\)"):
        E.get_plan(shape=(32, 32), backend="xla", fuse="pyramid",
                   cache=E.PlanCache())


def test_backend_rejects_unsupported_compute_dtype():
    class F16Less(B.Backend):
        name = "f16less-test"
        compute_dtypes = ("float32",)

    bk = B.register_backend(F16Less())
    try:
        key = E.PlanKey(wavelet="cdf97", scheme="ns-polyconv", levels=1,
                        shape=(16, 16), dtype="float32",
                        backend="f16less-test", optimize=False,
                        fuse="none", boundary="periodic",
                        compute_dtype="bfloat16")
        with pytest.raises(B.BackendError,
                           match=r"PlanKey\.compute_dtype='bfloat16'"):
            bk.validate(key)
    finally:
        B._REGISTRY.pop("f16less-test")


def test_register_backend_refuses_silent_override():
    with pytest.raises(ValueError, match="already registered"):
        B.register_backend(B.JnpBackend())


def test_registry_execute_entry_points():
    """Backend.execute / execute_inverse run a matching plan and reject
    a plan built for a different backend instead of silently running it
    on the wrong executor."""
    cache = E.PlanCache()
    x = _rand((16, 16), seed=11)
    plan = E.get_plan(shape=(16, 16), backend="xla", cache=cache)
    bk = B.get_backend("xla")
    pyr = bk.execute(plan, x)
    assert pyr.ll.shape == (8, 8)
    rec = bk.execute_inverse(plan, pyr)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(x),
                               rtol=1e-3, atol=1e-4)
    with pytest.raises(B.BackendError,
                       match=r"built for backend 'xla', not 'jnp'"):
        B.get_backend("jnp").execute(plan, x)
    with pytest.raises(B.BackendError, match=r"not 'pallas'"):
        B.get_backend("pallas").execute_inverse(plan, pyr)


def test_registry_is_the_dispatch_point():
    # no backend string branches left in the API layers: plans carry
    # their Backend object, and executors come from it
    plan = E.get_plan(shape=(16, 16), backend="xla", cache=E.PlanCache())
    assert plan.backend is B.get_backend("xla")
    import repro.core.transform
    import repro.tiling.api
    for mod in (repro.core.transform, repro.tiling.api):
        assert "backend ==" not in open(mod.__file__).read()


@pytest.mark.parametrize("platform,interpret", (
    ("cpu", True), ("tpu", False), ("gpu", False)))
def test_default_interpret_follows_platform(monkeypatch, platform,
                                            interpret):
    """The interpreter is the CPU path only; a TPU compiles the kernels
    with Mosaic, and any other platform has no Pallas path at all."""
    from repro.kernels import polyphase as PP
    monkeypatch.setattr(B.jax, "default_backend", lambda: platform)
    assert PP._default_interpret() is interpret
    key = E.PlanKey(WAVELET, "ns-polyconv", 2, (32, 32), "float32",
                    "pallas", False, "levels", "periodic")
    if platform == "gpu":
        with pytest.raises(B.BackendError, match="platform 'gpu'"):
            B.get_backend("pallas").validate(key)
    else:
        B.get_backend("pallas").validate(key)


def test_pyramid_refused_on_tpu_and_auto_avoids_it(monkeypatch):
    from repro.profiler import auto as PA
    key = E.PlanKey(WAVELET, "ns-polyconv", 3, (64, 64), "float32",
                    "pallas", False, "pyramid", "periodic")
    B.get_backend("pallas").validate(key)            # CPU: interpreter
    monkeypatch.setattr(B.jax, "default_backend", lambda: "tpu")
    with pytest.raises(B.BackendError, match="PlanKey.fuse='pyramid'"):
        B.get_backend("pallas").validate(key)
    auto = dataclasses.replace(key, backend="auto")
    assert ("pallas", "pyramid", "full") not in PA.enumerate_candidates(auto)


# ---------------------------------------------------------------------------
# Conv lowering (unit level)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", SCHEMES)
def test_conv_lowering_matches_program_walk(scheme):
    """The composed filter bank equals the roll-based program walk on
    random planes — per program, before any engine plumbing."""
    planes = tuple(_rand((2, 9, 7), seed=j) for j in range(4))
    for fuse in ("none", "scheme"):
        for inverse in (False, True):
            progs = C.compile_scheme_programs(WAVELET, scheme, False,
                                              inverse, "full", fuse)
            ref = list(planes)
            for p in progs:
                ref = CX.run_planes(p, ref)
            got = CV.run_planes_conv(progs, planes)
            for r, g in zip(ref, got):
                np.testing.assert_allclose(np.asarray(r), np.asarray(g),
                                           rtol=2e-5, atol=2e-5)


def test_conv_spec_geometry_and_stats():
    progs = C.compile_scheme_programs(WAVELET, "ns-conv", False, False,
                                      "full", "scheme")
    spec = CV.lower_program_to_conv(progs[0])
    assert spec.weights.shape[:2] == (4, 4)
    rn, rm = spec.pad
    assert spec.kernel_shape == (2 * rn + 1, 2 * rm + 1)
    assert spec.taps > 0
    st = CV.conv_stats([spec])
    assert st["convs"] == 1 and st["taps"] == spec.taps
    assert st["halo"] == max(spec.pad)
    # lowering is memoized per program
    assert CV.lower_program_to_conv(progs[0]) is spec


# ---------------------------------------------------------------------------
# XLA backend parity vs jnp (the acceptance matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("tap_opt", ("off", "exact", "full"))
def test_xla_matches_jnp_all_schemes_and_opt_levels(scheme, tap_opt):
    """6 schemes x tap_opt off/exact/full, 2 levels, batched, odd/prime
    plane dims (plane 2x: 22 = 2*11, 28 = 4*7)."""
    x = _rand((2, 44, 56), seed=3)
    kw = dict(wavelet=WAVELET, levels=2, scheme=scheme, tap_opt=tap_opt)
    ref = dwt2(x, backend="jnp", **kw)
    got = dwt2(x, backend="xla", **kw)
    _assert_pyramids_close(ref, got)
    rec = idwt2(got, wavelet=WAVELET, scheme=scheme, backend="xla",
                tap_opt=tap_opt)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(x),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("levels", (1, 2, 3))
def test_xla_levels_and_fuse_modes(levels):
    x = _rand((24, 40), seed=4)
    ref = dwt2(x, wavelet=WAVELET, levels=levels, backend="jnp")
    for fuse in ("none", "scheme", "levels"):
        got = dwt2(x, wavelet=WAVELET, levels=levels, backend="xla",
                   fuse=fuse)
        _assert_pyramids_close(ref, got)


def test_xla_batched_matches_per_image():
    x = _rand((3, 2, 32, 32), seed=5)
    batched = dwt2(x, levels=2, backend="xla", fuse="levels")
    single = dwt2(x[1, 0], levels=2, backend="xla", fuse="levels")
    np.testing.assert_allclose(np.asarray(batched.ll[1, 0]),
                               np.asarray(single.ll), rtol=2e-5, atol=2e-5)


def test_xla_optimized_section5_scheme():
    x = _rand((32, 48), seed=6)
    ref = dwt2(x, levels=2, scheme="ns-polyconv", optimize=True,
               backend="jnp")
    got = dwt2(x, levels=2, scheme="ns-polyconv", optimize=True,
               backend="xla")
    _assert_pyramids_close(ref, got)


def test_xla_bfloat16_compute_dtype():
    x = _rand((32, 32), seed=7)
    got = dwt2(x, levels=1, backend="xla", compute_dtype="bfloat16")
    ref = dwt2(x, levels=1, backend="jnp")
    assert got.ll.dtype == jnp.float32          # I/O dtype preserved
    np.testing.assert_allclose(np.asarray(ref.ll), np.asarray(got.ll),
                               rtol=0.05, atol=0.05)


def test_xla_tiled_matches_monolithic():
    x = _rand((64, 96), seed=8)
    mono = dwt2(x, levels=2, backend="xla")
    tiled = dwt2(x, levels=2, backend="xla", tiles=(32, 32))
    _assert_pyramids_close(mono, tiled)
    rec = idwt2(tiled, backend="xla", tiles=(32, 32))
    np.testing.assert_allclose(np.asarray(rec), np.asarray(x),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# Launch model: the barrier story on the third backend
# ---------------------------------------------------------------------------

def test_xla_conv_launches_follow_step_counts():
    cache = E.PlanCache()
    launches = {}
    for sc in ("sep-conv", "ns-conv", "ns-polyconv"):
        plan = E.get_plan(shape=(32, 32), levels=2, scheme=sc,
                          backend="xla", fuse="none", cache=cache)
        launches[sc] = plan.pallas_calls
        assert plan.pallas_calls == plan.num_steps
        fused = E.get_plan(shape=(32, 32), levels=2, scheme=sc,
                           backend="xla", fuse="scheme", cache=cache)
        assert fused.pallas_calls == 2          # one fused conv per level
    # ns-conv halves sep-conv's barriers — the paper's headline, now
    # measurable as conv launches
    assert launches["ns-conv"] == launches["sep-conv"] // 2


def test_jnp_backend_reports_zero_launches():
    plan = E.get_plan(shape=(32, 32), levels=2, backend="jnp",
                      cache=E.PlanCache())
    assert plan.pallas_calls == 0


def test_xla_hbm_model_positive_and_step_scaled():
    from repro.engine.plan import scheme_steps
    from repro.kernels import polyphase as PP
    sep = scheme_steps(WAVELET, "sep-conv", False, False)
    ns = scheme_steps(WAVELET, "ns-conv", False, False)
    kw = dict(itemsize=4, fuse="none", backend="xla")
    b_sep = PP.scheme_hbm_bytes(sep, (1024, 1024), **kw)
    b_ns = PP.scheme_hbm_bytes(ns, (1024, 1024), **kw)
    assert b_sep > 0 and b_ns > 0
    # fewer barrier convs -> fewer modelled HBM round trips
    assert b_ns < b_sep


def test_stats_exposes_capability_matrix():
    st = E.stats()
    names = [row["backend"] for row in st["backends"]]
    assert names == sorted(names) and "xla" in names
    xla = next(r for r in st["backends"] if r["backend"] == "xla")
    assert "pyramid" not in xla["fuse_modes"]
    assert not xla["pyramid_kernel"]
