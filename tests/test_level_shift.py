"""Integer samples through ``dwt2``: the JPEG 2000 DC level shift
(ISO/IEC 15444-1 Annex G.1.2) into float32 coefficients, on every
backend, against the float64 reference of the benchmark
(``bench/reference.py``, which imports nothing of ``repro``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.drivers.encode import reference_input
from repro import engine
from repro import telemetry as T
from repro.core import dwt2
from repro.core import schemes as S
from repro.engine import executor as X
from repro.kernels import polyphase as PP

U16 = {"samples": "uint16", "dtype": "float32", "bit_depth": 16}

#: max |got - ref| / max |ref| over the pyramid.  A float32 transform of
#: 16-bit samples reads ~1e-6 (rounding of ~20 MACs a coefficient, two
#: levels); its bfloat16 computation reads ~1e-2.  3e-5, the benchmark
#: cells' limit, lies between them with room on both sides.
REL_ERR = 3e-05


def _samples(shape, bits=16, seed=0):
    hi = 1 << bits
    return np.random.default_rng(seed).integers(0, hi, shape).astype(
        np.uint16)


def _rel_err(pyr, x, levels=2):
    ll, det = reference.dwt2(reference_input(x, U16), "cdf97", levels)
    want = reference.leaves(ll, det)
    got = [pyr.ll] + [b for d in pyr.details for b in d]
    worst = max(float(np.max(np.abs(np.asarray(g, np.float64) - w)))
                for g, w in zip(got, want))
    return worst / max(float(np.max(np.abs(w))) for w in want)


@pytest.mark.parametrize("backend", ["jnp", "xla", "pallas"])
@pytest.mark.parametrize("shape", [(60, 92), (2, 64, 128)],
                         ids=("60x92", "2x64x128"))
def test_uint16_samples_match_the_shifted_reference(shape, backend):
    """Float32 coefficients of ``x - 2**15`` within :data:`REL_ERR` of
    the reference; the same computed in bfloat16 (the benchmark's
    control) is not."""
    x = _samples(shape)
    pyr = dwt2(x, levels=2, backend=backend, fuse="levels", bit_depth=16)
    assert pyr.ll.dtype == jnp.float32
    assert all(b.dtype == jnp.float32 for d in pyr.details for b in d)
    assert _rel_err(pyr, x) <= REL_ERR
    control = dwt2(x, levels=2, backend=backend, fuse="levels",
                   bit_depth=16, compute_dtype="bfloat16")
    assert control.ll.dtype == jnp.float32
    assert _rel_err(control, x) > REL_ERR


@pytest.mark.parametrize("backend,fuse", [
    ("jnp", "none"), ("jnp", "pyramid"), ("xla", "none"),
    ("xla", "scheme"), ("pallas", "none"), ("pallas", "scheme"),
    ("pallas", "pyramid")])
def test_every_fuse_mode_shifts_as_the_float_path(backend, fuse):
    """Every fuse mode that ``dwt2`` accepts: ``uint16`` samples give
    the coefficients of their level-shifted float32 image bit for bit
    (the shift is exact in float32)."""
    x = _samples((32, 64), seed=1)
    got = dwt2(x, levels=2, backend=backend, fuse=fuse, bit_depth=16)
    want = dwt2(x.astype(np.float32) - 2.0 ** 15, levels=2,
                backend=backend, fuse=fuse)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


@pytest.mark.parametrize("backend", ["jnp", "xla", "pallas"])
def test_float_input_runs_the_unshifted_path_bit_for_bit(backend):
    """Float input at the DCI cell's CPU size: the same bits as the
    level functions composed by hand, split and steps, with no level
    shift in the program."""
    x = np.random.default_rng(2).normal(size=(3, 32, 64)).astype(
        np.float32) * 2048
    plan = engine.get_plan(shape=x.shape, levels=2, backend=backend,
                           fuse="levels", cache=engine.PlanCache())
    assert plan.key.bit_depth is None
    level = {"jnp": X.jnp_level_forward, "xla": X.xla_level_forward,
             "pallas": X.pallas_level_forward}[backend]

    def by_hand(v):
        details = []
        for spec in plan.level_specs:
            with jax.named_scope(f"dwt.level{spec.index}"):
                v, *det = level(v, spec, plan.key)
            details.append(tuple(det))
        return v, tuple(details[::-1])

    assert "level_shift" not in str(jax.make_jaxpr(plan._forward)(x))
    got = jax.tree_util.tree_leaves(plan._forward(x))
    want = jax.tree_util.tree_leaves(jax.jit(by_hand)(x))
    assert [np.asarray(g).tobytes() for g in got] == \
        [np.asarray(w).tobytes() for w in want]


@pytest.mark.parametrize("x,kw,match", [
    (np.zeros((8, 8), np.uint16), {}, "need bit_depth"),
    (np.zeros((8, 8), np.uint16), {"bit_depth": 17}, "does not fit"),
    (np.zeros((8, 8), np.uint8), {"bit_depth": 0}, "does not fit"),
    (np.zeros((8, 8), np.int32), {"bit_depth": 12}, "signed"),
    (np.zeros((8, 8), np.float32), {"bit_depth": 12}, "drop bit_depth"),
], ids=("no-bit-depth", "too-deep", "zero", "signed", "float"))
def test_sample_precision_is_checked_at_plan_build(x, kw, match):
    with pytest.raises(ValueError, match=match):
        dwt2(x, levels=1, **kw)


def test_integer_samples_are_refused_by_tiled_packet_and_3d_plans():
    from repro.core import dwt3, wpt2
    x = np.zeros((8, 16, 16), np.uint16)
    for call in (lambda: wpt2(x, packet="full:1"),
                 lambda: dwt3(x, levels=1),
                 lambda: dwt2(x, levels=1, tiles=(8, 8), bit_depth=16)):
        with pytest.raises(ValueError, match="monolithic 2-D forward"):
            call()


def test_plans_of_other_samples_are_other_cache_entries():
    cache = engine.PlanCache()
    kw = dict(shape=(32, 64), levels=2, cache=cache)
    plans = [engine.get_plan(dtype="uint16", bit_depth=16, **kw),
             engine.get_plan(dtype="uint16", bit_depth=12, **kw),
             engine.get_plan(dtype="float32", **kw),
             engine.get_plan(dtype="uint16", bit_depth=16, **kw)]
    assert len({id(p) for p in plans[:3]}) == 3 and plans[3] is plans[0]
    assert cache.stats()["misses"] == 3 and cache.stats()["hits"] == 1
    assert [p.key.bit_depth for p in plans[:3]] == [16, 12, None]


@pytest.mark.parametrize("width,paths", [(520, "kernel"), (92, "slices")])
def test_split_path_counter_names_each_levels_split(width, paths):
    """``repro_split_path_total`` counts each forward level's split once
    when the executable is traced: the kernel for widths of 256 and up
    (520, then 260: neither a multiple of 256), strided slices under."""
    x = _samples((8, width), seed=3)
    plan = engine.get_plan(shape=x.shape, dtype="uint16", bit_depth=16,
                           levels=2, backend="pallas", fuse="levels",
                           cache=engine.PlanCache())
    for _ in range(2):                  # the second call traces nothing
        plan.execute(x)
    assert T.snapshot()["repro_split_path_total"]["series"] == [
        {"labels": {"level": str(lvl), "path": paths}, "value": 1}
        for lvl in (0, 1)]


def test_level_shift_has_its_scope_on_the_jnp_path():
    T.set_mode("spans")
    plan = engine.get_plan(shape=(32, 64), dtype="uint16", bit_depth=16,
                           levels=2, backend="jnp", fuse="levels",
                           cache=engine.PlanCache())
    plan.execute(_samples((32, 64)))
    assert "dwt.level_shift" in set(T.op_scopes()["jit_dwt_forward"].values())


def test_level_shift_is_exact_in_float32_and_rounds_in_bfloat16():
    x = jnp.asarray(_samples((4, 256), seed=4))
    f32 = PP.level_shift(x, 16)
    assert f32.dtype == jnp.float32
    assert np.array_equal(np.asarray(f32),
                          np.asarray(x, np.float64) - 2.0 ** 15)
    bf16 = PP.level_shift(x, 16, jnp.bfloat16)
    assert bf16.dtype == jnp.float32
    want = (x.astype(jnp.bfloat16) - jnp.bfloat16(2.0 ** 15)).astype(
        jnp.float32)
    assert np.array_equal(np.asarray(bf16), np.asarray(want))
    assert not np.array_equal(np.asarray(bf16), np.asarray(f32))
    assert [np.asarray(p).tobytes() for p in S.to_planes(f32)] == \
        [np.asarray(p).tobytes() for p in PP.split_planes(x, 16)]
