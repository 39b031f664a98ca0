"""The benchmark's float64 reference against the engine, and the
comparison that decides ``correct``."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare
from bench import reference as R
from repro.core import dwt2, idwt2
from repro.engine.pyramid import Pyramid

#: the engine computes in float32 (epsilon 1.2e-7); a few dozen roundings
#: through the lifting steps stay under this
F32 = 1e-5


@pytest.mark.parametrize("wavelet", ["cdf97", "cdf53"])
@pytest.mark.parametrize("shape,levels", [((16, 32), 2),
                                          ((2, 3, 32, 64), 3)])
def test_reference_matches_engine(wavelet, shape, levels):
    x = np.random.default_rng(0).standard_normal(shape)
    ll, det = R.dwt2(x, wavelet, levels)
    got = dwt2(jnp.asarray(x, jnp.float32), wavelet=wavelet, levels=levels,
               backend="jnp")
    assert compare.rel_err(compare.pyramid_leaves(got),
                           R.leaves(ll, det)) < F32
    rec = idwt2(Pyramid(ll=jnp.asarray(ll, jnp.float32),
                        details=[tuple(jnp.asarray(b, jnp.float32)
                                       for b in d) for d in det]),
                wavelet=wavelet, backend="jnp")
    assert compare.rel_err([rec], [R.idwt2(ll, det, wavelet)]) < F32


@pytest.mark.parametrize("wavelet", ["cdf97", "cdf53"])
def test_reference_round_trip(wavelet):
    x = np.random.default_rng(1).integers(-2048, 2048, (3, 32, 64))
    ll, det = R.dwt2(x, wavelet, 3)
    assert np.max(np.abs(R.idwt2(ll, det, wavelet) - x)) < 1e-9


def test_reference_refuses_unknown_wavelet_and_shape():
    with pytest.raises(KeyError, match="dd137"):
        R.dwt2(np.zeros((8, 8)), "dd137", 1)
    with pytest.raises(ValueError, match="split"):
        R.dwt2(np.zeros((12, 16)), "cdf97", 3)


def test_encode_check_level_shifts_integer_samples_only():
    """ISO/IEC 15444-1 Annex G.1.2: an unsigned B-bit sample x enters
    the transform as x - 2**(B-1); float samples arrive shifted."""
    from bench.drivers import encode
    u16 = np.array([[0, 1, 32767], [32768, 40000, 65535]], np.uint16)
    got = encode.reference_input(u16, {"dtype": "float32",
                                       "samples": "uint16", "bit_depth": 16})
    assert got.dtype == np.float64
    assert np.array_equal(got, [[-32768, -32767, -1], [0, 7232, 32767]])
    u12 = np.array([0, 2048, 4095], np.uint16)
    assert np.array_equal(encode.reference_input(
        u12, {"dtype": "float32", "samples": "uint16", "bit_depth": 12}),
        [-2048, 0, 2047])
    f32 = np.array([-2048.0, 0.0, 2047.0], np.float32)
    got = encode.reference_input(f32, {"dtype": "float32", "bit_depth": 12})
    assert np.array_equal(got, f32) and got.dtype == np.float64


def test_rel_err_reads_the_worst_gap_over_the_largest_value():
    want = [np.array([4.0, -8.0]), np.array([[2.0]])]
    got = [np.array([4.0, -8.5]), np.array([[2.25]])]
    assert compare.rel_err(want, want) == 0.0
    assert compare.rel_err(got, want) == pytest.approx(0.5 / 8.0)
    with pytest.raises(ValueError, match="shape"):
        compare.rel_err([np.zeros(3)], [np.zeros(4)])
