"""Compiles of the main-path kernels for a described TPU v5e.

The TPU compiler ships with jaxlib's TPU plug-in, so a plan's executors
can be lowered and compiled for a v5e that is described, not attached.
That catches what the Pallas interpreter cannot: ref slices not aligned
to the (8, 128) tile, kernels over their scoped VMEM, programs that do
not fit the device.  Nothing runs, so results and times are not checked.

The topology is described inside a module fixture (never at import),
so only the test worker given this file loads the TPU library.  The
plan code asks ``jax.default_backend()`` for its platform, which here
is the CPU: the tests steer it to the TPU path themselves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine as E
from repro.engine import backends as B
from repro.engine import plan as PL
from repro.kernels import polyphase as PP

DCI4K = (3, 2160, 4096)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Build and trace plans as on a TPU: Mosaic-compiled kernels with
    tile-aligned blocks, no interpreter."""
    monkeypatch.setattr(PP, "_default_interpret", lambda: False)


def _key(scheme, backend, fuse, levels=1, wavelet="cdf97"):
    return PL.PlanKey(wavelet, scheme, levels, DCI4K, "float32", backend,
                      False, fuse, "periodic")


def _compile(plan, one_chip, inverse):
    x = jax.ShapeDtypeStruct(plan.key.shape, jnp.float32)
    if not inverse:
        return jax.jit(plan._forward).lower(jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip)).compile()
    pyr = jax.eval_shape(plan._forward, x)
    pyr = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        pyr)
    return jax.jit(plan._inverse).lower(*pyr).compile()


@pytest.mark.parametrize("inverse", (False, True), ids=("fwd", "inv"))
@pytest.mark.parametrize("scheme", ("ns-polyconv", "sep-lifting"))
def test_window_kernel_compiles_for_v5e(scheme, inverse, one_chip, mosaic):
    plan = PL.build_plan(_key(scheme, "pallas", "none"))
    bh, bw = plan.level_specs[0].block
    assert bh % PP.sublanes(jnp.float32) == 0 and bw % PP.LANES == 0
    text = _compile(plan, one_chip, inverse).as_text()
    assert "tpu_custom_call" in text          # the Pallas kernel is there
    # under its stable name, which the device trace shows
    assert f"%{PP.kernel_name(inverse, 0, 0)}" in text


@pytest.mark.parametrize("inverse", (False, True), ids=("fwd", "inv"))
def test_every_device_op_of_the_levels_path_has_a_scope(inverse, one_chip,
                                                       mosaic):
    """The benchmark's path (pallas, fuse="levels"): each instruction
    of the compiled entry computation, the ops the trace shows, maps to
    a ``dwt.*`` scope, and the kernels carry their names."""
    from repro.telemetry import scopes as SC
    plan = PL.build_plan(_key("ns-polyconv", "pallas", "levels", levels=2))
    text = _compile(plan, one_chip, inverse).as_text()
    _, scopes = SC.parse(text)
    idle = ("parameter(", "constant(", " tuple(", "get-tuple-element(")
    run = [ln.split(" = ")[0].split()[-1].lstrip("%")
           for ln in text[text.index("\nENTRY"):].splitlines()[1:]
           if " = " in ln and not any(k in ln for k in idle)]
    assert run and [o for o in run if o not in scopes] == []
    layer = "dwt.from_planes" if inverse else "dwt.to_planes"
    assert {layer, "dwt.pad", "dwt.level0", "dwt.level1"} <= set(
        scopes.values())
    for level in (0, 1):
        assert scopes[f"{PP.kernel_name(inverse, level, 0)}.1"] == \
            f"dwt.level{level}"


def test_levels_forward_splits_planes_without_a_gather(one_chip, mosaic):
    """The polyphase split compiles to the split kernel at each level,
    under ``dwt.to_planes``: an element-wise ``gather`` there took
    98.9 % of a DCI 4K forward on the chip."""
    from repro.telemetry import scopes as SC
    plan = PL.build_plan(_key("ns-polyconv", "pallas", "levels", levels=2))
    text = _compile(plan, one_chip, False).as_text()
    assert " gather(" not in text
    scopes = SC.parse(text)[1]
    split = [op for op in scopes if op.startswith(PP.SPLIT_KERNEL)]
    assert len(split) == 2
    assert {scopes[op] for op in split} == {"dwt.to_planes"}


@pytest.mark.parametrize("backend", ["jnp", "xla"])
def test_sharded_dwt2_off_pallas_compiles_for_v5e_2x2(backend, topo, mosaic,
                                                      monkeypatch):
    """``dwt2`` on the jnp and xla backends stays plain XLA ops, which
    GSPMD partitions over the 2x2 mesh (as the sharded train steps and
    the DWT gradient compression need).  A Mosaic kernel cannot be
    partitioned automatically: the split kernel belongs to the pallas
    backend alone."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core import transform as TR
    monkeypatch.setattr(B.jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    x = jax.ShapeDtypeStruct(
        (4, 512, 512), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec("data", None, "model")))
    fwd = jax.jit(lambda a: TR.dwt2(a, levels=2, backend=backend,
                                    fuse="levels"))
    text = fwd.lower(x).compile().as_text()
    assert "tpu_custom_call" not in text and " gather(" not in text


def test_pyramid_rejected_at_plan_build_on_tpu(monkeypatch, mosaic):
    monkeypatch.setattr(B.jax, "default_backend", lambda: "tpu")
    with pytest.raises(E.BackendError, match="PlanKey.fuse='pyramid'"):
        PL.build_plan(_key("ns-polyconv", "pallas", "pyramid", levels=4))


@pytest.mark.parametrize("inverse", (False, True), ids=("fwd", "inv"))
def test_xla_levels_compiles_for_v5e(inverse, one_chip, mosaic):
    plan = PL.build_plan(_key("ns-polyconv", "xla", "levels"))
    text = _compile(plan, one_chip, inverse).as_text()
    # float32 operands reach the MXU unrounded (bf16 passes lose parity)
    assert "operand_precision={highest,highest}" in text


S2_BAND = (10980, 10980)


def test_uint16_band_forward_compiles_with_the_split_kernel(one_chip,
                                                            mosaic):
    """A Sentinel-2 L1C 10 m band (10980², ``uint16``, 2 levels) through
    the Pallas forward: its split is the split kernel at both levels,
    level 0 reading the 16-bit samples themselves (the level shift runs
    in the kernel, no separate op), with no gather; one transform's
    temporaries stay under 4 GB."""
    import re
    from repro.telemetry import scopes as SC
    plan = PL.build_plan(PL.PlanKey("cdf97", "ns-polyconv", 2, S2_BAND,
                                    "uint16", "pallas", False, "levels",
                                    "periodic", bit_depth=16))
    compiled = jax.jit(plan._forward).lower(jax.ShapeDtypeStruct(
        S2_BAND, jnp.uint16, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    scopes = SC.parse(text)[1]
    split = [op for op in scopes if op.startswith(PP.SPLIT_KERNEL)]
    assert len(split) == 2
    assert {scopes[op] for op in split} == {"dwt.to_planes"}
    assert "dwt.level_shift" not in set(scopes.values())
    assert re.search(rf"%{PP.SPLIT_KERNEL}[.\d]* = .*custom-call.*"
                     rf"operand_layout_constraints={{u16\[1,10980,10980\]",
                     text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


@pytest.mark.parametrize("shape,grid,cols", [
    ((3, 2160, 4096), (3, 9, 4), 1024), ((3, 1080, 2048), (3, 5, 2), 1024),
    ((3, 540, 1024), (3, 3, 1), 1024), ((3, 270, 512), (3, 2, 1), 512)])
def test_dci_split_keeps_its_grid_and_blocks(shape, grid, cols, one_chip,
                                             mosaic):
    """At each level of the DCI 4K cell the split is the kernel it was
    with 256-multiple widths alone: the same grid and blocks."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    jaxpr = jax.make_jaxpr(PP.split_planes)(x)
    eqn, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    gm = eqn.params["grid_mapping"]
    blocks = [tuple(getattr(b, "block_size", b) for b in bm.block_shape)
              for bm in gm.block_mappings]
    assert gm.grid == grid
    assert blocks == [(1, 256, cols)] + [(1, 128, cols // 2)] * 4
    jax.jit(PP.split_planes).lower(jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=one_chip)).compile()
