"""Tap-program compiler: bit-identity, parity, op counts, geometry.

Deliverables covered:

* compiled ("off"/"exact") programs are **bit-identical** to the raw
  ``_apply_matrix_windows`` walk for all 6 schemes x optimize on/off x
  odd and prime-sized shapes — in-window and through the real Pallas
  dispatch path;
* the "full" pipeline (fold + CSE + rank-1) matches the raw walk to fp32
  tolerances (it reassociates sums, which is the point);
* op-count regression: compiled MACs never exceed the raw matrix count
  for any wavelet x scheme x optimize x fuse (the CI check), and the
  headline reduction — cdf97/ns-polyconv (optimize=False) >= 25% — holds;
* compute_dtype plumbing (bf16 parity tolerance) and the padded-plane
  HBM model fix.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import compiler as C
from repro.compiler import execute as X
from repro.core import schemes as S
from repro.core import transform as T
from repro.engine.plan import scheme_steps
from repro.kernels import ops as K
from repro.kernels import polyphase as PP

WNAMES = ("cdf53", "cdf97", "dd137")


def _rand(shape, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _windows(steps, hw, seed=0):
    r = sum(st.halo for st in steps)
    return r, [_rand((hw[0] + 2 * r, hw[1] + 2 * r), seed + k)
               for k in range(4)]


# ---------------------------------------------------------------------------
# Bit-identity of the exact pipeline vs the raw matrix walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", S.SCHEMES)
@pytest.mark.parametrize("optimize", (False, True))
@pytest.mark.parametrize("hw", ((15, 17), (37, 53)))   # odd / prime regions
def test_exact_program_bit_identical_to_raw_walk(scheme, optimize, hw):
    for wname in WNAMES:
        steps = scheme_steps(wname, scheme, optimize, False)
        r, xs = _windows(steps, hw)
        ref = PP._apply_steps_windows(steps, xs)
        for opt in ("off", "exact"):
            prog = C.compile_steps(steps, opt)
            out = X.run_window(prog, xs, r)
            for a, b in zip(ref, out):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", ((30, 34), (74, 106)))  # odd/prime planes
def test_exact_kernel_bit_identical_through_pallas(shape):
    """Through the real pallas_call path, block padding included."""
    x = _rand(shape, seed=1)
    for scheme in ("ns-polyconv", "sep-lifting"):
        raw = K.apply_scheme_pallas(x, wavelet="cdf97", scheme=scheme,
                                    block=(16, 32), tap_opt="off")
        ex = K.apply_scheme_pallas(x, wavelet="cdf97", scheme=scheme,
                                   block=(16, 32), tap_opt="exact")
        for a, b in zip(raw, ex):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Full pipeline: fp32 parity within reassociation tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", S.SCHEMES)
@pytest.mark.parametrize("optimize", (False, True))
def test_full_program_matches_raw_walk(scheme, optimize):
    for wname in WNAMES:
        steps = scheme_steps(wname, scheme, optimize, False)
        r, xs = _windows(steps, (21, 23), seed=2)
        ref = PP._apply_steps_windows(steps, xs)
        prog = C.compile_steps(steps, "full")
        out = X.run_window(prog, xs, r)
        for a, b in zip(ref, out):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_full_roundtrip_through_engine(backend):
    x = _rand((2, 32, 48), seed=3)
    pyr = T.dwt2(x, wavelet="cdf97", levels=2, scheme="ns-polyconv",
                 backend=backend, tap_opt="full")
    xr = T.idwt2(pyr, wavelet="cdf97", scheme="ns-polyconv",
                 backend=backend, tap_opt="full")
    np.testing.assert_allclose(np.asarray(xr), np.asarray(x),
                               rtol=1e-3, atol=1e-4)


def test_window_and_roll_executors_agree():
    """Same program, slice semantics vs periodic rolls: interior match."""
    steps = scheme_steps("cdf97", "ns-conv", False, False)
    prog = C.compile_steps(steps, "full")
    r = prog.halo
    planes = [_rand((12, 14), seed=4 + k) for k in range(4)]
    rolled = X.run_planes(prog, planes)
    # windows = periodic pad of the planes
    xs = [PP._periodic_pad(p, r, 12 + 2 * r, 14 + 2 * r) for p in planes]
    windowed = X.run_window(prog, xs, r)
    for a, b in zip(rolled, windowed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Op counts: the compiler must never lose, and must win where it claims
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wname", WNAMES)
@pytest.mark.parametrize("scheme", S.SCHEMES)
@pytest.mark.parametrize("optimize", (False, True))
@pytest.mark.parametrize("fuse", ("none", "scheme"))
def test_compiled_macs_never_exceed_raw(wname, scheme, optimize, fuse):
    """The CI op-count regression gate."""
    raw = C.program_stats(C.compile_scheme_programs(
        wname, scheme, optimize, False, "off", fuse))
    full = C.program_stats(C.compile_scheme_programs(
        wname, scheme, optimize, False, "full", fuse))
    assert full["macs"] <= raw["macs"]
    assert full["halo"] <= raw["halo"]


def test_headline_mac_reduction_ns_polyconv_cdf97():
    """Acceptance: >= 25% fewer MACs/pixel than the raw matrix walk."""
    raw = C.program_stats(C.compile_scheme_programs(
        "cdf97", "ns-polyconv", False, False, "off", "none"))
    full = C.program_stats(C.compile_scheme_programs(
        "cdf97", "ns-polyconv", False, False, "full", "none"))
    assert full["macs"] <= 0.75 * raw["macs"], (full, raw)


def test_exact_macs_match_paper_convention():
    """Lowered program MACs == the paper's count_ops for raw schemes."""
    for wname in WNAMES:
        for scheme in S.SCHEMES:
            sch = S.build_scheme(wname, scheme)
            progs = C.compile_scheme_programs(wname, scheme, False, False,
                                              "off", "none")
            assert C.program_stats(progs)["macs"] == sch.num_ops


def test_fused_lifting_halo_shrinks():
    """Per-axis margins: alternating H/V lifting steps need half the
    summed halo (8 halo-1 steps -> 4)."""
    steps = scheme_steps("cdf97", "sep-lifting", False, False)
    assert sum(st.halo for st in steps) == 8
    prog = C.compile_steps(steps, "full")
    assert prog.halo == 4


def test_required_margins_reject_small_windows():
    steps = scheme_steps("cdf97", "ns-conv", False, False)
    prog = C.compile_steps(steps, "full")
    with pytest.raises(ValueError):
        X.required_margins(prog, prog.halo - 1)


# ---------------------------------------------------------------------------
# compute_dtype plumbing (satellite: bf16 parity tolerance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_bf16_compute_dtype_parity(backend):
    x = _rand((32, 64), seed=5)
    ref = T.dwt2(x, wavelet="cdf97", levels=1, scheme="ns-polyconv",
                 backend=backend)
    bf = T.dwt2(x, wavelet="cdf97", levels=1, scheme="ns-polyconv",
                backend=backend, compute_dtype="bfloat16")
    assert bf.ll.dtype == jnp.float32          # I/O dtype is preserved
    # bf16 keeps ~2 decimal digits per op and cancellation can spike a
    # single sample, so parity is asserted in scaled norms: this checks
    # the plumbing, not bf16 precision
    for a, b in zip([ref.ll, *ref.details[0]], [bf.ll, *bf.details[0]]):
        a, b = np.asarray(a), np.asarray(b)
        scale = np.abs(a).max()
        assert np.abs(a - b).max() <= 0.15 * scale
        assert np.abs(a - b).mean() <= 0.03 * scale


def test_xla_conv_requests_full_float32_precision():
    """The TPU's default conv precision rounds float32 to bfloat16; the
    xla backend's convs must ask for HIGHEST to keep cross-backend parity."""
    import jax
    f = jax.jit(lambda v: T.dwt2(v, wavelet="cdf97", levels=2,
                                 scheme="ns-polyconv", backend="xla"))
    convs = [ln for ln in f.lower(_rand((32, 64), seed=6)).as_text()
             .splitlines() if "stablehlo.convolution" in ln]
    assert convs
    assert all("precision HIGHEST>, #stablehlo<precision HIGHEST" in ln
               for ln in convs)


def test_compute_dtype_is_part_of_plan_key():
    from repro import engine as E
    cache = E.PlanCache()
    kw = dict(wavelet="cdf53", scheme="ns-polyconv", levels=1,
              shape=(16, 16), dtype="float32", backend="jnp", cache=cache)
    E.get_plan(compute_dtype="float32", **kw)
    E.get_plan(compute_dtype="bfloat16", **kw)
    assert cache.stats()["misses"] == 2
    with pytest.raises(ValueError):
        E.get_plan(compute_dtype="float16", **kw)
    with pytest.raises(ValueError):
        E.get_plan(tap_opt="turbo", **kw)


# ---------------------------------------------------------------------------
# HBM model: padded-plane traffic (satellite)
# ---------------------------------------------------------------------------

def test_hbm_bytes_count_padded_plane_traffic():
    steps = scheme_steps("cdf97", "ns-polyconv", False, False)
    smooth = PP.scheme_hbm_bytes(steps, (2048, 2048), 4, block=(16, 32))
    # 2048 planes divide evenly: model unchanged by the fix
    bh, hp2 = PP._pick_block(1024, 16)
    assert (bh, hp2) == (16, 1024)
    # prime-ish plane dims (1019) pad to block multiples: the pad write,
    # pad-source read, and slice-back must all be counted
    prime = PP.scheme_hbm_bytes(steps, (2038, 2038), 4, block=(16, 32))
    hp = 1019
    bh, hp2 = PP._pick_block(hp, 16)
    assert hp2 > hp
    base = PP.scheme_hbm_bytes(steps, (2 * hp2, 2 * hp2), 4, block=(16, 32))
    # per call: pad (read hp*wp + write padded+halo) + slice (read padded
    # + write hp*wp) on four planes
    r = C.compile_steps(steps[:1], "full").halo
    extra = 0
    for st in steps:
        rr = C.compile_steps((st,), "full").halo
        extra += 4 * (hp * hp + (hp2 + 2 * rr) ** 2 + hp2 * hp2 + hp * hp)
    # the deinterleave pass scales with the true image size, so the two
    # shapes carry different split traffic
    split_diff = 2 * (2038 ** 2 - (2 * hp2) ** 2)
    assert prime == base + (extra + split_diff) * 4
    assert prime > smooth


def test_hbm_bytes_shrink_with_compiled_halo():
    """Compiled per-axis margins reduce modelled window reads."""
    steps = scheme_steps("cdf97", "sep-lifting", False, False)
    progs = C.compile_scheme_programs("cdf97", "sep-lifting", False, False,
                                      "full", "scheme")
    raw = PP.scheme_hbm_bytes(steps, (512, 512), 4, fuse="scheme",
                              block=(16, 32))
    compiled = PP.scheme_hbm_bytes(steps, (512, 512), 4, fuse="scheme",
                                   block=(16, 32), programs=progs)
    assert compiled < raw
