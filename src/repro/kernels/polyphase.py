"""Generic Pallas TPU kernel for polyphase-matrix DWT steps.

TPU adaptation of the paper's execution model (DESIGN.md §2):

* one scheme *step* (barrier)  ->  one ``pl.pallas_call``: the four
  polyphase planes make one full round trip through HBM; batched input
  rides a leading grid dimension (one launch covers the whole batch);
* GPU on-chip shared memory     ->  a VMEM scratch window per plane, filled
  by an explicit ``pltpu.make_async_copy`` DMA of the block + halo from a
  wrap-padded HBM plane (inputs stay in HBM; windows are whole tiles);
* GPU threads                   ->  the 8x128 VPU vector lanes; every filter
  tap lowers to one shifted static slice + multiply-add over the whole
  block, so the per-pixel MAC count *is* the paper's operation count;
* the Section 5 optimization    ->  constant (halo-0) matrices are applied
  elementwise on the loaded window (pre) or on the output block (post),
  adding no halo and no HBM traffic — "computed without any barrier".

Beyond the paper, ``fuse="scheme"`` executes *all* steps of a scheme in a
single ``pallas_call`` using overlapped-tile recompute: the window is loaded
with the compound halo (sum of per-step halos) and each step shrinks the
valid region.  On a GPU this is impossible (threads cannot exchange halo
values without a barrier); on TPU the halo is simply recomputed locally,
reducing *every* scheme to one HBM round trip.  See EXPERIMENTS.md §Perf.

Two further escalations of the same idea:

* **fused pyramid** (:func:`pyramid_forward_pallas` /
  :func:`pyramid_inverse_pallas`) — the *whole multi-level transform* in
  one ``pallas_call``: compound-halo windows of the interleaved image,
  polyphase split/merge via static strided slices in-VMEM, per-level
  margins stacked by :mod:`repro.compiler.pyramid` so every in-window
  split stays phase-aligned with the monolithic transform;
* **double-buffered windows** — every kernel here owns two VMEM scratch
  slots per input and starts the next grid block's DMA before the
  current block's compute (the TPU grid is sequential per core), so
  copies overlap arithmetic across the entire grid.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import poly as P
from repro.core import optimize as O
from repro.core import schemes as S
from repro import compiler as C
from repro import telemetry as T
from repro.compiler import execute as CX

def _default_interpret() -> bool:
    """Kernels run through the Pallas interpreter on the CPU platform and
    are compiled by Mosaic on a TPU.  Any other platform has no Pallas
    TPU path: the pallas backend rejects it at plan build."""
    return jax.default_backend() == "cpu"


#: minor (lane) tile of a TPU array
LANES = 128

#: scoped VMEM each kernel may use.  Mosaic's default scope on a v5e is
#: 16 MiB, which the larger tap programs overflow at the default block
#: (cdf97 ns-conv needs ~19 MiB at a 128x512 block); the chip has
#: 128 MiB of VMEM per core.
VMEM_LIMIT_BYTES = 64 * 2 ** 20
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def sublanes(dtype) -> int:
    """Second-minor (sublane) tile of a TPU array: 8 rows of a 32-bit
    dtype, 16 of a 16-bit one."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def tile(dtype, interpret: bool) -> Tuple[int, int]:
    """``(rows, cols)`` alignment of blocks and windows: the TPU tile
    when Mosaic compiles the kernel, none in the interpreter.  There,
    128-lane windows only add work, and they change XLA:CPU's code
    enough that batched and single-image results differ in the last bit,
    which the serve contract pins equal."""
    return (1, 1) if interpret else (sublanes(dtype), LANES)


@dataclasses.dataclass(frozen=True)
class StepSpec:
    """Matrices of one barrier-delimited step (hashable, static)."""

    pre: Tuple[P.Matrix, ...]
    main: Optional[P.Matrix]
    post: Tuple[P.Matrix, ...]

    @property
    def halo(self) -> int:
        return P.matrix_halo(self.main) if self.main is not None else 0


def steps_of(scheme_obj) -> List[StepSpec]:
    """Normalize a Scheme / OptScheme into a list of StepSpecs."""
    if isinstance(scheme_obj, O.OptScheme):
        return [StepSpec(tuple(st.pre), st.main, tuple(st.post))
                for st in scheme_obj.steps]
    return [StepSpec((), m, ()) for m, _ in scheme_obj.steps]


# ---------------------------------------------------------------------------
# In-window algebra (traced inside the kernel; all slices static)
# ---------------------------------------------------------------------------

def _apply_matrix_windows(m: P.Matrix, xs: Sequence[jax.Array], h: int
                          ) -> List[jax.Array]:
    """Apply a polyphase matrix to four equally-shaped windows.

    ``h`` is the halo consumed by this matrix: outputs are smaller by 2h on
    each axis.  Tap (km, kn) of entry (i, j) reads
    ``xs[j][h - kn : h - kn + oh, h - km : h - km + ow]``
    (y[n] = sum_k g_k x[n-k]).
    """
    oh = xs[0].shape[0] - 2 * h
    ow = xs[0].shape[1] - 2 * h
    outs: List[jax.Array] = []
    for i in range(4):
        acc = None
        for j in range(4):
            for (km, kn), c in sorted(m[i][j].items()):
                r0, c0 = h - kn, h - km
                term = xs[j][r0:r0 + oh, c0:c0 + ow]
                if not (i == j and (km, kn) == (0, 0) and c == 1.0):
                    term = term * c
                acc = term if acc is None else acc + term
        outs.append(acc if acc is not None
                    else jnp.zeros((oh, ow), xs[0].dtype))
    return outs


def _apply_steps_windows(steps: Sequence[StepSpec], xs: Sequence[jax.Array]
                         ) -> List[jax.Array]:
    """Run a fused step sequence over windows, shrinking by each halo."""
    cur = list(xs)
    for st in steps:
        for m in st.pre:
            cur = _apply_matrix_windows(m, cur, 0)
        if st.main is not None:
            cur = _apply_matrix_windows(st.main, cur, st.halo)
        for m in st.post:
            cur = _apply_matrix_windows(m, cur, 0)
    return cur


# ---------------------------------------------------------------------------
# The pallas_call
# ---------------------------------------------------------------------------

def _pick_block_aligned(n: int, target: int, align: int) -> Tuple[int, int]:
    """``(b, n_padded)`` with the block edge ``b`` a multiple of ``align``
    near ``target``: an exact divisor of ``n`` when one is at least half
    the target, else the target with ``n`` padded to a block multiple.
    ``align`` is the TPU tile for the window kernels (via
    :func:`_pick_block`) and ``2^levels`` for the fused-pyramid kernel,
    so every window start is phase-aligned at every pyramid level."""
    t = max(align, (min(n, target) // align) * align)
    d = t
    while d >= align and n % d:
        d -= align
    if d >= align and 2 * d >= t:
        return d, n
    return t, -(-n // t) * t


def _pipeline_ids(grid: Tuple[int, int, int]):
    """Current/next grid-block ids for double-buffered DMA windows.

    The TPU grid runs sequentially per core (last dim fastest), so block
    ``t``'s compute can overlap block ``t+1``'s copy.  Returns
    ``(t, slot, (b, i, j), t1, slot1, (b1, i1, j1), total)`` where
    ``slot``/``slot1`` alternate between the two scratch buffers.
    """
    nb, ni, nj = grid
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    t = (b * ni + i) * nj + j
    t1 = t + 1
    b1 = t1 // (ni * nj)
    r1 = jax.lax.rem(t1, ni * nj)
    return (t, jax.lax.rem(t, 2), (b, i, j),
            t1, jax.lax.rem(t1, 2), (b1, r1 // nj, jax.lax.rem(r1, nj)),
            nb * ni * nj)


def _pick_block(n: int, target: int, align: int = 1) -> Tuple[int, int]:
    """Block edge and padded plane size for one axis: ``(b, n_padded)``.

    An axis no longer than ``target`` is one block spanning it (a block
    equal to the whole axis needs no tile alignment).  A longer axis is
    cut into ``align``-multiple blocks (the TPU tile: :func:`sublanes`
    rows, :data:`LANES` columns): an exact divisor close to the target
    when one exists, else the target-size block with the plane padded
    up to the next block multiple — the caller slices the output back
    to ``n``.  Non-smooth plane dims therefore never degrade to tiny
    blocks.
    """
    if n <= target:
        return n, n
    return _pick_block_aligned(n, target, align)


def _window(block: int, halo: int, align: int) -> int:
    """Edge of the window DMA'd per block along one axis: the block plus
    both halos, rounded up to the tile (Mosaic copies whole tiles only,
    even when one block spans the axis)."""
    return -(-(block + 2 * halo) // align) * align


def _block_ds(idx, n_blocks: int, block: int, size: int, align: int):
    """Ref slice of block ``idx``'s window: a static whole-axis slice for
    a single block, else a tile-aligned dynamic start."""
    if n_blocks == 1:
        return pl.ds(0, size)
    return pl.ds(pl.multiple_of(idx * block, align), size)


def _periodic_pad(p: jax.Array, lo: int, rows: int, cols: int) -> jax.Array:
    """Periodic extension of a plane (..., hp, wp) to (..., rows, cols),
    starting ``lo`` samples before the origin on both axes.

    Every output sample holds the periodic (mod hp / mod wp) extension of
    the *original* plane, so block padding and window rounding never
    change boundary semantics.  Its device ops carry the ``dwt.pad``
    scope.
    """
    hp, wp = p.shape[-2:]
    if (lo, rows, cols) == (0, hp, wp):
        return p
    cfg = [(0, 0)] * (p.ndim - 2) + [(lo, rows - hp - lo),
                                     (lo, cols - wp - lo)]
    with jax.named_scope("dwt.pad"):
        return jnp.pad(p, cfg, mode="wrap")


def kernel_name(inverse: bool, level: int, step: int) -> str:
    """The stable name of one window kernel (``pallas_call``), which
    the device trace shows: ``dwt_fwd_l<level>_s<step>`` or
    ``dwt_inv_l<level>_s<step>``."""
    return f"dwt_{'inv' if inverse else 'fwd'}_l{level}_s{step}"


def _steps_pallas_call(steps: Tuple[StepSpec, ...], planes, *,
                       block: Tuple[int, int], interpret: Optional[bool],
                       name: str, compute_dtype=jnp.float32,
                       program: Optional[C.TapProgram] = None):
    """One pallas_call, named ``name``, executing ``steps`` (fused) over
    the four planes.

    ``planes`` are batched ``(B, hp, wp)``; the batch is the leading grid
    dimension, so one call covers the whole batch with no vmap round trip.

    With a compiled ``program`` the kernel body executes the tap program
    (fewer MACs, and a halo from the program's per-axis margin analysis —
    never larger than the summed step halos); without one it walks the
    raw matrices, which is the compiler's bit-identity reference.

    The window copies are double-buffered: each plane has two VMEM
    scratch slots and the next grid block's DMA is started before the
    current block's compute, so the copy of window ``t+1`` overlaps the
    arithmetic of window ``t`` across the whole (sequential) grid.
    """
    if interpret is None:
        interpret = _default_interpret()
    r_total = program.halo if program is not None \
        else sum(st.halo for st in steps)
    nb, hp, wp = planes[0].shape
    out_dtype = planes[0].dtype
    th, tw = tile(out_dtype, interpret)
    bh, hp2 = _pick_block(hp, block[0], th)
    bw, wp2 = _pick_block(wp, block[1], tw)
    ni, nj = hp2 // bh, wp2 // bw
    grid = (nb, ni, nj)
    win = (_window(bh, r_total, th), _window(bw, r_total, tw))
    padded = [_periodic_pad(p, r_total, hp2 - bh + win[0],
                            wp2 - bw + win[1]) for p in planes]

    def kernel(*refs):
        x_refs = refs[:4]
        o_refs = refs[4:8]
        scratch = refs[8:12]
        sems = refs[12]
        t, slot, cur, t1, slot1, nxt, total = _pipeline_ids(grid)

        def dmas(slot, ids):
            bb, ii, jj = ids
            return [pltpu.make_async_copy(
                x_refs[k].at[bb, _block_ds(ii, ni, bh, win[0], th),
                             _block_ds(jj, nj, bw, win[1], tw)],
                scratch[k].at[slot],
                sems.at[slot, k],
            ) for k in range(4)]

        @pl.when(t == 0)
        def _():
            for cp in dmas(slot, cur):
                cp.start()

        @pl.when(t1 < total)
        def _():
            for cp in dmas(slot1, nxt):
                cp.start()

        for cp in dmas(slot, cur):
            cp.wait()
        xs = [s[slot].astype(compute_dtype) for s in scratch]
        if program is not None:
            ys = CX.run_window(program, xs, r_total)
        else:
            ys = _apply_steps_windows(steps, xs)
        # a tile-rounded window computes a few extra rows/columns
        for k in range(4):
            o_refs[k][0, :, :] = ys[k][:bh, :bw].astype(out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM) for _ in range(4)],
        out_specs=[pl.BlockSpec((1, bh, bw), lambda b, i, j: (b, i, j))
                   for _ in range(4)],
        out_shape=[jax.ShapeDtypeStruct((nb, hp2, wp2), out_dtype)
                   for _ in range(4)],
        scratch_shapes=[pltpu.VMEM((2,) + win, out_dtype)
                        for _ in range(4)]
        + [pltpu.SemaphoreType.DMA((2, 4))],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=name,
    )(*padded)
    if (hp2, wp2) != (hp, wp):
        with jax.named_scope("dwt.crop"):
            out = [o[:, :hp, :wp] for o in out]
    return tuple(out)


def apply_steps_pallas(steps: Sequence[StepSpec], planes, *,
                       fuse: str = "none",
                       block: Tuple[int, int] = (256, 512),
                       interpret: Optional[bool] = None,
                       compute_dtype=jnp.float32,
                       tap_opt: str = "full",
                       programs: Optional[Tuple[C.TapProgram, ...]] = None,
                       level: int = 0, inverse: bool = False):
    """Execute a scheme's steps on the four polyphase planes.

    ``planes`` may carry arbitrary leading batch dims ``(..., hp, wp)``;
    they are flattened into the kernel's leading grid dimension.

    fuse="none"   — paper-faithful: one pallas_call (HBM round trip) per
                    step; the step count is the paper's barrier count.
    fuse="scheme" — beyond-paper: a single pallas_call with compound halo
                    (overlapped-tile recompute).

    ``tap_opt`` selects the tap-program compilation level ("off" walks the
    raw matrices — the seed behaviour and the compiler's bit-identity
    reference; "exact" compiles without reassociation; "full" applies all
    passes).  Pre-compiled ``programs`` (one per pallas_call under the
    chosen fuse mode, e.g. from a :class:`repro.engine.plan.DwtPlan`)
    skip recompilation.  ``level`` and ``inverse`` only name the kernels
    (:func:`kernel_name`).
    """
    steps = tuple(steps)
    if fuse not in ("none", "scheme"):
        raise ValueError(f"unknown fuse mode {fuse!r}")
    if programs is None and tap_opt != "off":
        if fuse == "scheme":
            programs = (C.compile_steps(steps, tap_opt),)
        else:
            programs = tuple(C.compile_steps((st,), tap_opt)
                             for st in steps)
    planes = tuple(jnp.asarray(p) for p in planes)
    batch = planes[0].shape[:-2]
    p3 = [p.reshape((-1,) + p.shape[-2:]) for p in planes]
    if fuse == "scheme":
        p3 = _steps_pallas_call(steps, p3, block=block,
                                interpret=interpret,
                                name=kernel_name(inverse, level, 0),
                                compute_dtype=compute_dtype,
                                program=programs[0] if programs else None)
    else:
        for i, st in enumerate(steps):
            p3 = _steps_pallas_call((st,), p3, block=block,
                                    interpret=interpret,
                                    name=kernel_name(inverse, level, i),
                                    compute_dtype=compute_dtype,
                                    program=programs[i] if programs
                                    else None)
    return tuple(p.reshape(batch + p.shape[-2:]) for p in p3)


# ---------------------------------------------------------------------------
# The polyphase split of the Pallas forward
# ---------------------------------------------------------------------------

#: name of the split kernel (``pallas_call``) in the device trace
SPLIT_KERNEL = "dwt_split"

#: which split each forward level took, counted once per level when a
#: forward executable is traced (nothing is counted per call)
SPLIT_PATHS = T.counter(
    "repro_split_path_total",
    "polyphase splits of traced forward levels, by path: the split "
    "kernel or strided slices", labelnames=("path", "level"))


def count_split(x, path: str, level: int) -> None:
    """Count one level's split (``path`` ``"kernel"`` or ``"slices"``)
    while a forward executable is traced; eager calls are not counted."""
    if isinstance(x, jax.core.Tracer):
        SPLIT_PATHS.inc(path=path, level=level)


def _shift(v, bit_depth: int, compute_dtype):
    """``v - 2**(bit_depth - 1)`` computed in ``compute_dtype``, held as
    float32 (exact for float32 compute and up to 24-bit samples).  Only
    float32 arithmetic and casts, so the split kernel runs it too."""
    cdt = jnp.dtype(compute_dtype)
    if jnp.issubdtype(v.dtype, jnp.unsignedinteger) and v.dtype.itemsize < 4:
        v = v.astype(jnp.int32)   # Mosaic casts no unsigned int to float
    v = v.astype(jnp.float32)
    if cdt != jnp.float32:
        v = v.astype(cdt).astype(jnp.float32)
    v = v - float(2 ** (bit_depth - 1))
    if cdt != jnp.float32:
        v = v.astype(cdt).astype(jnp.float32)
    return v


def level_shift(x: jax.Array, bit_depth: int, compute_dtype=jnp.float32):
    """Integer samples as the transform sees them: ``x - 2**(bit_depth -
    1)`` (the DC level shift of ISO/IEC 15444-1 Annex G.1.2), as float32,
    one element-wise op under the ``dwt.level_shift`` scope."""
    with jax.named_scope("dwt.level_shift"):
        return _shift(x, bit_depth, compute_dtype)


def split_fits(x, bit_depth: Optional[int] = None) -> bool:
    """The shapes :func:`split_planes` takes: ``(..., H, W)`` with even H
    and even W of at least 256 (one column block of 128 plane lanes);
    32-bit samples, or with ``bit_depth`` unsigned 16-bit ones, which
    the kernel level-shifts into float32 planes."""
    h, w = jnp.shape(x)[-2:]
    dt = jnp.dtype(x.dtype)
    if bit_depth is None:
        fits_dtype = dt.itemsize == 4
    else:
        fits_dtype = (dt.itemsize == 2
                      and jnp.issubdtype(dt, jnp.unsignedinteger))
    return fits_dtype and h % 2 == 0 and w % 2 == 0 and w >= 2 * LANES


def _split_kernel(x_ref, o00, o01, o10, o11, t_ref, *, chunks, shift):
    """One (2L, chunks * 2L) block of the image into four
    (L, chunks * L) blocks of the planes, L = 128.  Mosaic loads a
    sublane stride only from a 128-lane buffer and no lane stride at
    all, so each 128-lane column strip goes through ``t_ref``: rows
    split by a strided load; columns by a transpose, a strided load and
    a transpose back.  ``shift`` (or None) maps a strip of samples to
    the planes' dtype on its way into ``t_ref``.  A block past the
    image's last column reads undefined samples into columns that are
    never written back: output column k reads input columns 2k and
    2k + 1 alone."""
    n = LANES
    outs = ((o00, o01), (o10, o11))
    for k in range(chunks):
        rows = ([], [])               # rows[i][c]: rows i::2 of strip c
        for c in range(2):
            strip = x_ref[0, :, pl.ds((2 * k + c) * n, n)]
            t_ref[...] = strip if shift is None else shift(strip)
            for i in range(2):
                rows[i].append(t_ref[pl.ds(i, n, stride=2), :])
        for i in range(2):
            t_ref[pl.ds(0, n), :] = rows[i][0].T
            t_ref[pl.ds(n, n), :] = rows[i][1].T   # columns on sublanes
            for j in range(2):
                outs[i][j][0, :, pl.ds(k * n, n)] = \
                    t_ref[pl.ds(j, n, stride=2), :].T


def to_planes(x: jax.Array, bit_depth: Optional[int] = None,
              compute_dtype=jnp.float32, level: int = 0):
    """The polyphase split of a Pallas forward level: the split kernel
    where :func:`split_fits`, else :func:`repro.core.schemes.to_planes`
    (strided slices); both under the ``dwt.to_planes`` scope.  Integer
    samples (``bit_depth``) are level-shifted by the kernel as it reads
    them, or else by :func:`level_shift` first.  The path taken counts
    in ``repro_split_path_total`` (:func:`count_split`)."""
    if bit_depth is not None and not split_fits(x, bit_depth):
        x, bit_depth = level_shift(x, bit_depth, compute_dtype), None
    if not split_fits(x, bit_depth):
        count_split(x, "slices", level)
        return S.to_planes(x)
    count_split(x, "kernel", level)
    with jax.named_scope("dwt.to_planes"):
        return split_planes(x, bit_depth, compute_dtype)


def split_planes(x: jax.Array, bit_depth: Optional[int] = None,
                 compute_dtype=jnp.float32):
    """``x[..., i::2, j::2]`` for (i, j) in (0,0), (0,1), (1,0), (1,1),
    bit for bit, as one Pallas kernel: one HBM read and one write of
    the image (``split_fits(x, bit_depth)`` must hold).  A strided index
    or slice of the lane axis is a gather or a per-element copy on the
    TPU.  With ``bit_depth`` the planes are the level-shifted samples
    in float32 (:func:`level_shift`), computed as they are read.

    Column blocks are ``chunks`` 256-column strips: as many as divide W
    up to 4 where W is a multiple of 256, else 4 (fewer on a narrower
    image) with the last block partial, as the last row block is."""
    *lead, h, w = x.shape
    xb = x.reshape(-1, h, w)
    strips = w // (2 * LANES)
    chunks = math.gcd(strips, 4) if w % (2 * LANES) == 0 \
        else min(strips, 4)
    bw = 2 * LANES * chunks
    if bit_depth is None:
        out_dtype, shift = x.dtype, None
    else:
        out_dtype = jnp.float32
        shift = functools.partial(_shift, bit_depth=bit_depth,
                                  compute_dtype=compute_dtype)
    plane = jax.ShapeDtypeStruct((xb.shape[0], h // 2, w // 2), out_dtype)
    out_spec = pl.BlockSpec((1, LANES, bw // 2), lambda b, i, j: (b, i, j))
    planes = pl.pallas_call(
        functools.partial(_split_kernel, chunks=chunks, shift=shift),
        grid=(xb.shape[0], pl.cdiv(h // 2, LANES), pl.cdiv(w, bw)),
        in_specs=[pl.BlockSpec((1, 2 * LANES, bw),
                               lambda b, i, j: (b, i, j))],
        out_specs=[out_spec] * 4,
        out_shape=[plane] * 4,
        scratch_shapes=[pltpu.VMEM((2 * LANES, LANES), out_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=_default_interpret(),
        name=SPLIT_KERNEL,
    )(xb)
    return tuple(p.reshape(*lead, h // 2, w // 2) for p in planes)


# ---------------------------------------------------------------------------
# Fused-pyramid megakernel: the whole multi-level transform in one call
# ---------------------------------------------------------------------------

def pyramid_out_levels(levels: int) -> List[int]:
    """Pyramid-kernel I/O layout: the level of each subband slot, in
    order — coarsest LL first, then (HL, LH, HH) per level finest-first.
    Shared by the forward/inverse kernels, the VMEM estimate, and the
    HBM model so the four can never drift apart."""
    return [levels - 1] + [l for l in range(levels) for _ in range(3)]


def _split(x: jax.Array) -> List[jax.Array]:
    """In-window polyphase split: four static strided slices (no HBM
    gather — the deinterleave happens on the VMEM-resident window)."""
    return [x[0::2, 0::2], x[0::2, 1::2], x[1::2, 0::2], x[1::2, 1::2]]


def _interleave(planes: Sequence[jax.Array]) -> jax.Array:
    """In-window polyphase merge (inverse of :func:`_split`)."""
    x1, x2, x3, x4 = planes
    a, b = x1.shape
    top = jnp.stack([x1, x2], axis=-1).reshape(a, 2 * b)
    bot = jnp.stack([x3, x4], axis=-1).reshape(a, 2 * b)
    return jnp.stack([top, bot], axis=-2).reshape(2 * a, 2 * b)


def _run_level_window(steps, program, xs, shrink, compute_dtype):
    """One level of in-window work shrinking by exactly ``shrink``.

    With a program, :func:`~repro.compiler.execute.run_window` absorbs
    any alignment slack (``shrink >= program.halo``) into its margin
    analysis; the raw matrix walk shrinks by the summed step halos, so
    the slack is sliced off afterwards — keeping every mode's output at
    the same, schedule-chosen offset.
    """
    if program is not None:
        return CX.run_window(program, xs, shrink)
    ys = _apply_steps_windows(steps, xs)
    d = shrink - sum(st.halo for st in steps)
    if d:
        ys = [y[d:y.shape[0] - d, d:y.shape[1] - d] for y in ys]
    return ys


def pyramid_forward_pallas(x, *, levels: int, steps: Tuple[StepSpec, ...],
                           sched, programs=None,
                           block: Tuple[int, int] = (256, 512),
                           interpret: Optional[bool] = None,
                           compute_dtype=jnp.float32):
    """Whole multi-level forward DWT as a **single** ``pallas_call``.

    Per grid block, the kernel DMAs one compound-halo window of the
    *interleaved* image (halo = ``sched.margins[0]``, the stacked
    multi-level margin), splits it into polyphase planes in-VMEM via
    static strided slices (no ``to_planes`` HBM pass), runs the level-0
    program, then re-splits the in-window LL and runs deeper levels on
    the shrinking valid region — the LL plane never touches HBM until
    the coarsest level.  Per-level subbands are written straight to
    their pyramid outputs, and the window copies are double-buffered
    across the grid exactly like :func:`_steps_pallas_call`.

    ``sched`` is a forward :class:`~repro.compiler.pyramid.PyramidSchedule`
    (phase-aligned shrinks — see that module for the margin algebra).
    Returns ``(ll, details)`` with details **finest-first**.
    """
    if interpret is None:
        interpret = _default_interpret()
    x = jnp.asarray(x)
    batch = x.shape[:-2]
    h, w = x.shape[-2:]
    align = 1 << levels
    bh, hp2 = _pick_block_aligned(h, 2 * block[0], align)
    bw, wp2 = _pick_block_aligned(w, 2 * block[1], align)
    x3 = x.reshape((-1, h, w))
    nb = x3.shape[0]
    out_dtype = x3.dtype
    M = sched.margins[0]
    win = (bh + 2 * M, bw + 2 * M)
    grid = (nb, hp2 // bh, wp2 // bw)
    padded = _periodic_pad(x3, M, hp2 + 2 * M, wp2 + 2 * M)

    out_levels = pyramid_out_levels(levels)
    out_specs = [pl.BlockSpec((1, bh >> (l + 1), bw >> (l + 1)),
                              lambda b, i, j: (b, i, j))
                 for l in out_levels]
    out_shape = [jax.ShapeDtypeStruct(
        (nb, hp2 >> (l + 1), wp2 >> (l + 1)), out_dtype)
        for l in out_levels]

    def kernel(x_ref, *refs):
        o_refs = refs[:1 + 3 * levels]
        scratch = refs[-2]
        sems = refs[-1]
        t, slot, cur_ids, t1, slot1, nxt_ids, total = _pipeline_ids(grid)

        def dma(slot, ids):
            bb, ii, jj = ids
            return pltpu.make_async_copy(
                x_ref.at[bb, pl.ds(ii * bh, win[0]), pl.ds(jj * bw, win[1])],
                scratch.at[slot],
                sems.at[slot],
            )

        @pl.when(t == 0)
        def _():
            dma(slot, cur_ids).start()

        @pl.when(t1 < total)
        def _():
            dma(slot1, nxt_ids).start()

        dma(slot, cur_ids).wait()
        cur = scratch[slot].astype(compute_dtype)
        for l in range(levels):
            ys = _run_level_window(steps, programs[l] if programs else None,
                                   _split(cur), sched.shrinks[l],
                                   compute_dtype)
            m1 = sched.margins[l + 1]
            ch, cw = bh >> (l + 1), bw >> (l + 1)
            for k in range(1, 4):
                o_refs[1 + 3 * l + k - 1][0, :, :] = \
                    ys[k][m1:m1 + ch, m1:m1 + cw].astype(out_dtype)
            cur = ys[0]
            if l + 1 < levels and cur.dtype != out_dtype:
                # value parity with per-level kernels, where the LL plane
                # round-trips through the I/O dtype between levels
                cur = cur.astype(out_dtype).astype(compute_dtype)
        mL = sched.margins[levels]
        o_refs[0][0, :, :] = cur[mL:mL + (bh >> levels),
                                 mL:mL + (bw >> levels)].astype(out_dtype)

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2,) + win, out_dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
        name="dwt_pyramid_fwd",
    )(padded)

    def clip(o, l):
        o = o[:, :h >> (l + 1), :w >> (l + 1)]
        return o.reshape(batch + o.shape[-2:])

    with jax.named_scope("dwt.crop"):
        ll = clip(outs[0], levels - 1)
        details = tuple(tuple(clip(outs[1 + 3 * l + d], l)
                              for d in range(3)) for l in range(levels))
    return ll, details


def pyramid_inverse_pallas(ll, details, *, levels: int,
                           steps: Tuple[StepSpec, ...], sched,
                           programs=None,
                           block: Tuple[int, int] = (256, 512),
                           interpret: Optional[bool] = None,
                           compute_dtype=jnp.float32):
    """Whole multi-level inverse DWT as a single ``pallas_call``.

    ``details`` is finest-first (matching :func:`pyramid_forward_pallas`).
    Per grid block the kernel DMAs the coarsest-LL window plus one
    window per subband per level (margins from the inverse
    :class:`~repro.compiler.pyramid.PyramidSchedule`), reconstructs the
    coarsest level in-VMEM, re-interleaves via static stacking (no
    ``from_planes`` HBM pass), and walks down to the full-resolution
    block — the intermediate LL planes never touch HBM.
    """
    if interpret is None:
        interpret = _default_interpret()
    ll = jnp.asarray(ll)
    batch = ll.shape[:-2]
    h, w = ll.shape[-2] << levels, ll.shape[-1] << levels
    align = 1 << levels
    bh, hp2 = _pick_block_aligned(h, 2 * block[0], align)
    bw, wp2 = _pick_block_aligned(w, 2 * block[1], align)
    out_dtype = ll.dtype
    # level-l windows carry margin margins[l+1] (the LL one margins[L])
    n_in = 1 + 3 * levels
    in_levels = pyramid_out_levels(levels)
    in_margins = [sched.margins[levels]] + \
        [sched.margins[l + 1] for l in in_levels[1:]]
    planes = [ll] + [d for det in details for d in det]
    cores = [(bh >> (l + 1), bw >> (l + 1)) for l in in_levels]
    wins = [(ch + 2 * m, cw + 2 * m)
            for (ch, cw), m in zip(cores, in_margins)]
    padded = []
    for p, l, m in zip(planes, in_levels, in_margins):
        p3 = jnp.asarray(p).reshape((-1,) + p.shape[-2:])
        padded.append(_periodic_pad(p3, m, (hp2 >> (l + 1)) + 2 * m,
                                    (wp2 >> (l + 1)) + 2 * m))
    nb = padded[0].shape[0]
    grid = (nb, hp2 // bh, wp2 // bw)

    def kernel(*refs):
        x_refs = refs[:n_in]
        o_ref = refs[n_in]
        scratch = refs[n_in + 1:2 * n_in + 1]
        sems = refs[-1]
        t, slot, cur_ids, t1, slot1, nxt_ids, total = _pipeline_ids(grid)

        def dmas(slot, ids):
            bb, ii, jj = ids
            return [pltpu.make_async_copy(
                x_refs[k].at[bb, pl.ds(ii * cores[k][0], wins[k][0]),
                             pl.ds(jj * cores[k][1], wins[k][1])],
                scratch[k].at[slot],
                sems.at[slot, k],
            ) for k in range(n_in)]

        @pl.when(t == 0)
        def _():
            for cp in dmas(slot, cur_ids):
                cp.start()

        @pl.when(t1 < total)
        def _():
            for cp in dmas(slot1, nxt_ids):
                cp.start()

        for cp in dmas(slot, cur_ids):
            cp.wait()
        cur = scratch[0][slot].astype(compute_dtype)
        for l in range(levels - 1, -1, -1):
            xs = [cur] + [scratch[1 + 3 * l + d][slot].astype(compute_dtype)
                          for d in range(3)]
            ys = _run_level_window(steps, programs[l] if programs else None,
                                   xs, sched.shrinks[l], compute_dtype)
            cur = _interleave(ys)
            if l > 0 and cur.dtype != out_dtype:
                cur = cur.astype(out_dtype).astype(compute_dtype)
        o_ref[0, :, :] = cur.astype(out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in range(n_in)],
        out_specs=pl.BlockSpec((1, bh, bw), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, hp2, wp2), out_dtype),
        scratch_shapes=[pltpu.VMEM((2,) + wn, out_dtype) for wn in wins]
        + [pltpu.SemaphoreType.DMA((2, n_in))],
        interpret=interpret,
        name="dwt_pyramid_inv",
    )(*padded)
    with jax.named_scope("dwt.crop"):
        return out[:, :h, :w].reshape(batch + (h, w))


def pyramid_vmem_bytes(levels: int, win_shapes: Sequence[Tuple[int, int]],
                       itemsize: int, compute_itemsize: int = 4) -> int:
    """Rough VMEM footprint of one fused-pyramid kernel instance: the
    double-buffered input window scratch plus ~3 finest-window-sized
    compute intermediates (the split planes, the level outputs, and the
    live LL carry)."""
    io = 2 * sum(wh * ww for wh, ww in win_shapes) * itemsize
    wh0, ww0 = max(win_shapes, key=lambda s: s[0] * s[1])
    return io + 3 * wh0 * ww0 * compute_itemsize


# ---------------------------------------------------------------------------
# Analytic HBM-traffic model (used by the roofline benchmarks)
# ---------------------------------------------------------------------------

def scheme_hbm_bytes(steps: Sequence[StepSpec], shape: Tuple[int, int],
                     itemsize: int, fuse: str = "none",
                     block: Tuple[int, int] = (256, 512),
                     programs: Optional[Sequence] = None,
                     split_merge: bool = True,
                     backend: str = "pallas") -> int:
    """Ideal HBM bytes moved by one transform level on a (H, W) image.

    ``backend="pallas"`` (default) models the window kernels below;
    ``backend="xla"`` models the grouped-conv executor instead: per conv
    (= per barrier step under ``fuse="none"``, one fused conv under any
    other mode) the four planes are periodically pre-padded by the
    program halo (read the planes, write the padded copies) and the conv
    reads the padded planes and writes the four outputs — no block
    decomposition, the conv emitter tiles internally.

    Per pallas_call: read 4 planes (block+halo windows, overlap counted)
    + write 4 planes.  When ``_pick_block`` pads a non-smooth plane dim,
    each call really writes the padded ``hp2 x wp2`` planes and the
    caller pads the inputs (one extra read+write of every plane) and
    slices the outputs back (another read+write): that traffic is
    counted, so the roofline model matches what the kernel actually
    moves.  The halo-only wrap copy on *unpadded* planes is still
    excluded — production kernels fold it into wrapped corner DMAs; it
    is identical across schemes and does not change the comparison.

    ``split_merge`` counts the polyphase deinterleave (``to_planes``,
    forward) / reinterleave (``from_planes``, inverse) that every
    non-pyramid plan actually pays per transform: one extra read + write
    of the full image, as a separate pass outside the level kernels.
    The fused-pyramid kernel splits/merges in-VMEM and is modelled by
    :func:`pyramid_hbm_bytes`, which omits it.

    ``programs`` (one compiled tap program per call group) narrows the
    halo to the compiled per-axis margin when available.
    """
    h, w = shape
    hp, wp = h // 2, w // 2
    # any level-granularity fuse mode ("scheme"/"levels") is one fused
    # launch per level; only "none" runs one launch per barrier step
    groups = [[st] for st in steps] if fuse == "none" else [steps]
    if backend == "xla":
        total = 0
        for gi, g in enumerate(groups):
            r = (programs[gi].halo if programs is not None
                 else sum(st.halo for st in g))
            # periodic pre-pad: read 4 planes, write 4 padded planes ...
            read = 4 * hp * wp
            write = 4 * (hp + 2 * r) * (wp + 2 * r)
            # ... then the grouped conv reads them and writes 4 planes
            read += 4 * (hp + 2 * r) * (wp + 2 * r)
            write += 4 * hp * wp
            total += (read + write) * itemsize
        if split_merge:
            total += 2 * h * w * itemsize
        return total
    bh, hp2 = _pick_block(hp, block[0])
    bw, wp2 = _pick_block(wp, block[1])
    padded = (hp2, wp2) != (hp, wp)
    total = 0
    for gi, g in enumerate(groups):
        if programs is not None:
            r = programs[gi].halo
        else:
            r = sum(st.halo for st in g)
        read = 4 * (hp2 // bh) * (wp2 // bw) * (bh + 2 * r) * (bw + 2 * r)
        write = 4 * hp2 * wp2
        if padded:
            # _periodic_pad materializes (hp2+2r) x (wp2+2r) planes ...
            read += 4 * hp * wp
            write += 4 * (hp2 + 2 * r) * (wp2 + 2 * r)
            # ... and the padded outputs are sliced back to hp x wp
            read += 4 * hp2 * wp2
            write += 4 * hp * wp
        total += (read + write) * itemsize
    if split_merge:
        # to_planes / from_planes: read the interleaved image, write the
        # four planes (or vice versa) — once per transform
        total += 2 * h * w * itemsize
    return total


def pyramid_hbm_bytes(steps: Sequence[StepSpec], shape: Tuple[int, int],
                      itemsize: int, levels: int, fuse: str = "pyramid",
                      block: Tuple[int, int] = (256, 512),
                      programs: Optional[Sequence] = None) -> int:
    """Ideal HBM bytes of one multi-level forward transform per fuse mode.

    ``fuse in ("none", "scheme", "levels")`` sums the per-level model of
    :func:`scheme_hbm_bytes` (including the per-level deinterleave pass
    — the LL plane round-trips through HBM between levels).  ``fuse ==
    "pyramid"`` models the megakernel: the padded interleaved image is
    materialized once, each grid block reads one compound-halo window
    (overlap counted), and every subband is written exactly once — no
    split/merge passes and no inter-level LL traffic at all.
    """
    h, w = shape
    if fuse != "pyramid":
        kfuse = "none" if fuse == "none" else "scheme"
        return sum(scheme_hbm_bytes(steps, (h >> l, w >> l), itemsize,
                                    fuse=kfuse, block=block,
                                    programs=programs)
                   for l in range(levels))
    reaches = C.level_reaches(steps, programs, levels)
    sched = C.forward_schedule(reaches, levels)
    align = 1 << levels
    bh, hp2 = _pick_block_aligned(h, 2 * block[0], align)
    bw, wp2 = _pick_block_aligned(w, 2 * block[1], align)
    M = sched.margins[0]
    # padded-image materialization: read the image, write the padded copy
    total = h * w + (hp2 + 2 * M) * (wp2 + 2 * M)
    # one compound-halo window read per block; every subband written once
    total += (hp2 // bh) * (wp2 // bw) * (bh + 2 * M) * (bw + 2 * M)
    out_levels = pyramid_out_levels(levels)
    outs = [(hp2 >> (l + 1)) * (wp2 >> (l + 1)) for l in out_levels]
    total += sum(outs)
    if (hp2, wp2) != (h, w):
        # padded outputs are sliced back to the true subband dims
        total += sum(outs)
        total += sum((h >> (l + 1)) * (w >> (l + 1)) for l in out_levels)
    return total * itemsize
