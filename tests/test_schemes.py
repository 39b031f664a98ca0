"""The paper's central claims at the scheme level.

* all six schemes compute identical coefficients (Section 4: "they all
  compute the same values");
* the step counts halve for the non-separable variants (Table 1);
* the Section 5 optimization reproduces the paper's operation counts
  (Table 1, OpenCL column) exactly for 13 of its 14 cells.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.compiler import temporal as T
from repro.core import optimize as O
from repro.core import poly as P
from repro.core import schemes as S
from repro.core.wavelets import WAVELETS

WNAMES = sorted(WAVELETS)

# Paper Table 1: (steps, OpenCL ops) for the optimized schemes.
PAPER_TABLE1 = {
    ("cdf53", "sep-conv"): (2, 20),
    ("cdf53", "sep-lifting"): (4, 16),
    ("cdf53", "ns-conv"): (1, 23),
    ("cdf53", "ns-lifting"): (2, 18),
    ("cdf97", "sep-conv"): (2, 56),
    ("cdf97", "sep-lifting"): (8, 32),
    ("cdf97", "ns-conv"): (1, 152),
    ("cdf97", "ns-polyconv"): (2, 46),
    ("cdf97", "ns-lifting"): (4, 36),
    ("dd137", "sep-conv"): (2, 60),
    ("dd137", "sep-lifting"): (4, 32),
    ("dd137", "ns-conv"): (1, 203),
    ("dd137", "ns-lifting"): (2, 50),
}
# The one knowingly-diverging cell: paper reports 20 for CDF 9/7 separable
# polyconvolution (register reuse across steps); our convention gives 40.
PAPER_DIVERGENT = {("cdf97", "sep-polyconv"): (4, 20, 40)}


@pytest.mark.parametrize("wname", WNAMES)
def test_total_matrices_identical(wname):
    ref = S.build_scheme(wname, "sep-lifting").total_matrix()
    for sc in S.SCHEMES:
        got = S.build_scheme(wname, sc).total_matrix()
        assert P.mat_max_diff(got, ref) < 1e-9, sc


@pytest.mark.parametrize("wname", WNAMES)
def test_optimized_matrices_identical(wname):
    ref = S.build_scheme(wname, "sep-lifting").total_matrix()
    for sc in S.SCHEMES:
        got = O.build_optimized(wname, sc).total_matrix()
        assert P.mat_max_diff(got, ref) < 1e-9, sc


@pytest.mark.parametrize("wname", WNAMES)
def test_numeric_equivalence_all_schemes(wname):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 96)), dtype=jnp.float32)
    ref = S.forward(x, wname, "sep-lifting")
    for sc in S.SCHEMES:
        y = S.forward(x, wname, sc)
        yo = O.forward_optimized(x, wname, sc)
        for a, b, c in zip(ref, y, yo):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-4, atol=2e-5)


def test_step_halving():
    """The paper's headline: non-separable fusion halves step counts."""
    for wname in WNAMES:
        k = WAVELETS[wname].K
        assert S.build_scheme(wname, "sep-conv").num_steps == 2
        assert S.build_scheme(wname, "ns-conv").num_steps == 1
        assert S.build_scheme(wname, "sep-lifting").num_steps == 4 * k
        assert S.build_scheme(wname, "ns-lifting").num_steps == 2 * k
        assert S.build_scheme(wname, "sep-polyconv").num_steps == 2 * k
        assert S.build_scheme(wname, "ns-polyconv").num_steps == k


@pytest.mark.parametrize("key", sorted(PAPER_TABLE1))
def test_table1_opencl_ops_exact(key):
    wname, sc = key
    steps, paper_ops = PAPER_TABLE1[key]
    t = O.table1_ops(wname, sc)
    assert t["steps"] == steps
    assert t["ops_adapted"] == paper_ops, t


def test_table1_divergent_cell_documented():
    for (wname, sc), (steps, paper, ours) in PAPER_DIVERGENT.items():
        t = O.table1_ops(wname, sc)
        assert t["steps"] == steps
        assert t["ops_adapted"] == ours  # our counting convention


def test_raw_ns_conv_count_cdf97():
    """Raw (unoptimized) ns-conv for CDF 9/7 = 81+63+63+49 = 256 MACs,
    the filter sizes of the paper's Figure 3."""
    t = O.table1_ops("cdf97", "ns-conv")
    assert t["ops_raw"] == 256


@pytest.mark.parametrize("wname", WNAMES)
@pytest.mark.parametrize("sc", S.SCHEMES)
def test_inverse_scheme_is_exact_inverse(wname, sc):
    fwd = S.build_scheme(wname, sc).total_matrix()
    inv = S.build_inverse_scheme(wname, sc).total_matrix()
    assert P.mat_max_diff(P.matmul(inv, fwd), P.identity()) < 1e-9


def test_polyconv_equals_conv_for_single_pair():
    """Paper: polyconvolution 'makes sense only when K > 1'."""
    for wname in ("cdf53", "dd137"):
        a = S.build_scheme(wname, "ns-conv")
        b = S.build_scheme(wname, "ns-polyconv")
        assert a.num_steps == b.num_steps == 1
        assert P.mat_max_diff(a.total_matrix(), b.total_matrix()) < 1e-9


def _bits(a):
    a = np.asarray(a)
    return a.shape, a.dtype, a.tobytes()


def _seeded(shape, dtype, seed=0):
    x = np.random.default_rng(seed).normal(size=shape) * 1000
    return jnp.asarray(x).astype(dtype)


@pytest.mark.parametrize("hw", [(8, 8), (6, 10), (7, 10), (8, 9)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
@pytest.mark.parametrize("lead", [(), (3,), (2, 3)], ids=("2d", "c", "bc"))
def test_to_planes_is_the_strided_index_bit_for_bit(lead, dtype, hw):
    """``to_planes`` (strided slices) returns exactly ``x[..., i::2,
    j::2]``: shapes, dtypes and bits, eager and jitted, odd H or W
    included; on even H and W ``from_planes`` undoes it."""
    x = _seeded(lead + hw, dtype)
    want = [np.asarray(x)[..., i::2, j::2] for i in (0, 1) for j in (0, 1)]
    for split in (S.to_planes, jax.jit(S.to_planes)):
        got = split(x)
        assert [_bits(g) for g in got] == [_bits(w) for w in want]
    if hw[0] % 2 == 0 and hw[1] % 2 == 0:
        assert _bits(S.from_planes(S.to_planes(x))) == _bits(x)


@pytest.mark.parametrize("hw", [(8, 256), (36, 512), (270, 768)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("lead", [(), (3,), (2, 3)], ids=("2d", "c", "bc"))
def test_split_kernel_is_the_strided_index_bit_for_bit(lead, dtype, hw):
    """The TPU's split kernel (run by the Pallas interpreter here)
    returns exactly ``x[..., i::2, j::2]``, NaN, -0 and inf included,
    over partial row blocks and 1, 2 or 3 column strips."""
    from repro.kernels import polyphase as PP
    x = _seeded(lead + hw, dtype)
    if dtype == "float32":
        x = x.at[..., 1, 3].set(jnp.nan).at[..., 2, 5].set(-0.0) \
            .at[..., 5, 130].set(-jnp.inf)
    assert PP.split_fits(x)
    want = [np.asarray(x)[..., i::2, j::2] for i in (0, 1) for j in (0, 1)]
    got = PP.split_planes(x)
    assert [_bits(g) for g in got] == [_bits(w) for w in want]


@pytest.mark.parametrize("shape,dtype,kernel", [
    ((3, 36, 512), "float32", True),
    ((36, 256), "int32", True),
    ((3, 36, 384), "float32", False),     # W not a multiple of 256
    ((3, 35, 512), "float32", False),     # odd H
    ((3, 36, 512), "bfloat16", False),    # 16-bit
])
def test_pallas_split_takes_the_kernel_where_it_fits(shape, dtype, kernel):
    """The Pallas forward's split runs the split kernel for the shapes
    it fits and strided slices for the rest; both give the strided
    index.  ``core.schemes.to_planes`` never launches a kernel."""
    from repro.kernels import polyphase as PP
    x = _seeded(shape, dtype)
    assert ("pallas_call" in str(jax.make_jaxpr(PP.to_planes)(x))) == kernel
    assert "pallas_call" not in str(jax.make_jaxpr(S.to_planes)(x))
    want = [np.asarray(x)[..., i::2, j::2] for i in (0, 1) for j in (0, 1)]
    assert [_bits(g) for g in PP.to_planes(x)] == [_bits(w) for w in want]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
@pytest.mark.parametrize("lead", [(), (3,), (2, 3)], ids=("3d", "c", "bc"))
def test_temporal_split_is_the_strided_index_bit_for_bit(lead, dtype):
    """``temporal_split`` (strided slices) returns exactly ``x[..., i::2,
    :, :]`` on the time axis, and ``temporal_merge`` undoes it."""
    x = _seeded(lead + (6, 4, 5), dtype)
    s, d = T.temporal_split(x)
    assert [_bits(s), _bits(d)] == [
        _bits(np.asarray(x)[..., i::2, :, :]) for i in (0, 1)]
    assert _bits(T.temporal_merge(s, d)) == _bits(x)
