"""DWT execution plans: *what* to compute, resolved once per configuration.

The scheme algebra (``repro.core.schemes`` / ``repro.core.optimize``) says
*what* a transform is — a sequence of 4x4 polyphase-matrix steps.  The seed
implementation re-ran that algebra (pure-Python Laurent-polynomial
products) on every ``dwt2`` call and re-decided block shapes on every
``pallas_call``.  A :class:`DwtPlan` does all of that exactly once per

    (wavelet, scheme, levels, shape, dtype, backend, optimize, fuse,
     boundary, ..., bit_depth)

key: per-level :class:`~repro.kernels.polyphase.StepSpec` sequences
(forward and inverse), per-level block shapes and halo pads, and the
compiled executor callables.  Plans are cheap to hold and are shared
through the LRU cache in :mod:`repro.engine.cache`, so repeated
same-configuration calls have zero rebuild cost.

Execution semantics (see :mod:`repro.engine.backends` /
:mod:`repro.engine.executor`):

* every registered backend accepts batched ``(..., H, W)`` input;
* ``fuse="none"``   — paper-faithful: one barrier (pallas_call) per step;
* ``fuse="scheme"`` — one pallas_call per level (compound halo);
* ``fuse="levels"`` — the whole multi-level pyramid is a single traced
  computation: level kernels are chained without returning to Python
  between levels, and each level runs as one fused kernel;
* ``fuse="pyramid"`` — the whole multi-level pyramid is a **single
  pallas_call**: polyphase split/merge happens in-VMEM on compound-halo
  windows of the interleaved image and the LL plane never touches HBM
  between levels (see :mod:`repro.kernels.polyphase` /
  :mod:`repro.compiler.pyramid`).  A VMEM-budget guard falls back to
  ``"levels"`` execution when the compound window would not fit
  (``$REPRO_PYRAMID_VMEM_LIMIT`` bytes, default 12 MiB); on the jnp
  backend, ``"pyramid"`` runs the eager per-level chain (bit-identical
  to ``fuse="none"`` — there is no kernel granularity to fuse).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

# the leaf pyramid module must be imported before anything from repro.core:
# repro.core.__init__ imports transform, which imports it back
from repro.engine.pyramid import Pyramid, Pyramid3, WaveletPacket2D

from repro.core import optimize as O
from repro.core import schemes as S
from repro.kernels import polyphase as PP
from repro import compiler as C
from repro import telemetry as T
from repro.engine import autotune
from repro.engine import backends as B
from repro.faults import degrade as R
from repro.faults import inject as FI

FUSE_MODES = ("none", "scheme", "levels", "pyramid")
BOUNDARIES = ("periodic",)
COMPUTE_DTYPES = ("float32", "bfloat16")


def coefficient_dtype(dtype: str) -> str:
    """The dtype a forward transform of ``dtype`` samples writes:
    float32 for integer samples (level-shifted), else ``dtype`` itself
    (I/O stays in the input dtype)."""
    integer = jnp.issubdtype(jnp.dtype(dtype), jnp.integer)
    return "float32" if integer else dtype


def validate_samples(key: "PlanKey") -> None:
    """Integer samples need their precision, ``bit_depth``: the forward
    transform sees ``x - 2**(bit_depth - 1)`` (the DC level shift of
    ISO/IEC 15444-1 Annex G.1.2) and writes float32 coefficients.  Float
    samples are taken as already shifted and take no ``bit_depth``."""
    dt = jnp.dtype(key.dtype)
    if not jnp.issubdtype(dt, jnp.integer):
        if key.bit_depth is not None:
            raise ValueError(
                f"bit_depth={key.bit_depth} is the precision of integer "
                f"samples; float input (dtype {dt.name}) is taken as "
                f"already level-shifted: drop bit_depth")
        return
    if key.packet is not None or key.ndim == 3 or key.tiles is not None:
        raise ValueError(
            f"integer samples (dtype {dt.name}) are taken by the "
            f"monolithic 2-D forward alone (dwt2 with bit_depth); packet, "
            f"3-D and tiled transforms take float samples, level-shifted")
    if key.bit_depth is None:
        raise ValueError(
            f"integer samples (dtype {dt.name}) need bit_depth, their "
            f"precision in bits: dwt2(x, bit_depth=B) transforms "
            f"x - 2**(B-1) into float32 coefficients")
    if not jnp.issubdtype(dt, jnp.unsignedinteger):
        raise ValueError(
            f"integer samples of dtype {dt.name} are signed: the level "
            f"shift (bit_depth) is for unsigned samples; pass signed "
            f"samples as float")
    bits = 8 * dt.itemsize
    if not 1 <= key.bit_depth <= bits:
        raise ValueError(
            f"bit_depth={key.bit_depth} does not fit dtype {dt.name}: "
            f"it holds 1 to {bits} bits")


PYRAMID_VMEM_LIMIT_ENV = "REPRO_PYRAMID_VMEM_LIMIT"
# budget for the rough estimate of PP.pyramid_vmem_bytes (I/O windows +
# three compute windows).  Mosaic's real scoped VMEM runs ~5x over such
# an estimate (cdf97 ns-conv window kernel, 128x512 block: ~3.8 MiB
# estimated, 18.84 MiB compiled for a v5e), so 12 MiB stays inside the
# PP.VMEM_LIMIT_BYTES (64 MiB) the kernels request.
DEFAULT_PYRAMID_VMEM_LIMIT = 12 * 2 ** 20

# engine-wide observability, on the central telemetry registry
# (surfaced through repro.engine.stats() and the Prometheus exposition)
PYRAMID_LAUNCHES = T.counter(
    "repro_pyramid_kernel_launches_total",
    "fused-pyramid megakernel launches (single-pallas_call executions)")
VMEM_FALLBACKS = T.counter(
    "repro_vmem_fallbacks_total",
    "fuse='pyramid' plans demoted to fuse='levels' by the VMEM guard")
PLAN_BUILDS = T.counter(
    "repro_plan_builds_total", "DwtPlan builds (plan-cache misses + "
    "direct build_plan calls)", labelnames=("backend", "fuse", "scheme"))
EXECUTIONS = T.counter(
    "repro_plan_executions_total", "plan executions",
    labelnames=("op", "backend", "fuse", "scheme"))
WORKLOAD_DEMOTIONS = T.counter(
    "repro_workload_fuse_demotions_total",
    "fuse='pyramid' plans demoted to fuse='levels' because the megakernel "
    "is 2-D-pyramid-only (packet / 3-D workloads)",
    labelnames=("workload", "backend"))

#: deprecated dict-style alias of the pre-telemetry module counters
#: (``COUNTERS["pyramid_kernel_launches"]`` etc.); will be removed one
#: release after PR 8 — read the registry instead (docs/observability.md)
COUNTERS = T.CounterAlias({
    "pyramid_kernel_launches": ("repro_pyramid_kernel_launches_total", {}),
    "vmem_fallbacks": ("repro_vmem_fallbacks_total", {}),
})


def pyramid_vmem_limit() -> int:
    """Configurable VMEM budget for the fused-pyramid kernel."""
    v = os.environ.get(PYRAMID_VMEM_LIMIT_ENV)
    return int(v) if v else DEFAULT_PYRAMID_VMEM_LIMIT


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Everything that determines a compiled execution plan.

    ``compute_dtype`` is the in-kernel arithmetic dtype (I/O stays in the
    array dtype, but for integer samples: :func:`coefficient_dtype`);
    ``tap_opt`` is the tap-program compilation level
    ("off" = raw matrix walk, "exact" = bit-preserving compilation,
    "full" = fold + CSE + rank-1 factorization).
    """

    wavelet: str
    scheme: str
    levels: int
    shape: Tuple[int, ...]  # full input shape, batch dims included
    dtype: str
    backend: str
    optimize: bool
    fuse: str
    boundary: str
    compute_dtype: str = "float32"
    tap_opt: str = "full"
    # (tile_h, tile_w) core size for tiled execution, or None (monolithic).
    # Part of the key so tiled plans cache exactly like monolithic ones.
    tiles: Optional[Tuple[int, int]] = None
    # canonical packet-tree leaf paths (repro.core.packets.PacketTree),
    # or None for the plain LL-recursion pyramid; when set, ``levels``
    # equals the tree depth and ``shape`` stays (..., H, W)
    packet: Optional[Tuple[str, ...]] = None
    # 2 = image (..., H, W); 3 = volume (..., T, H, W) — the t+2D
    # transform (1-D temporal lifting + 2-D per half-band, per level)
    ndim: int = 2
    # precision of integer samples (``dtype`` an unsigned integer), which
    # the forward level-shifts by 2**(bit_depth-1); None for float input
    bit_depth: Optional[int] = None


def max_feasible_levels(h: int, w: int) -> int:
    """Largest pyramid depth for an (h, w) image: both dims must stay
    divisible by 2 at every level (min trailing-zero count)."""
    def tz(n: int) -> int:
        return (n & -n).bit_length() - 1 if n > 0 else 0
    return min(tz(h), tz(w))


def validate_image_geometry(h: int, w: int, levels: int) -> None:
    """Check image dims against ``levels`` with an actionable error that
    names the offending dimension and the max feasible levels, instead
    of failing deep inside kernel tracing."""
    div = 1 << levels
    for name, n in (("H", h), ("W", w)):
        if n % div:
            raise ValueError(
                f"levels={levels} infeasible for image {h}x{w}: {name}={n} "
                f"is not divisible by 2^levels={div}; max feasible levels "
                f"for this image is {max_feasible_levels(h, w)}")


@functools.lru_cache(maxsize=512)
def scheme_steps(wavelet: str, scheme: str, optimize: bool,
                 inverse: bool) -> Tuple[PP.StepSpec, ...]:
    """Scheme algebra -> StepSpec sequence, memoized across all plans."""
    if inverse:
        return tuple(PP.steps_of(S.build_inverse_scheme(wavelet, scheme)))
    sch = (O.build_optimized(wavelet, scheme) if optimize
           else S.build_scheme(wavelet, scheme))
    return tuple(PP.steps_of(sch))


@dataclasses.dataclass
class LevelSpec:
    """Static execution parameters of one pyramid level."""

    index: int                        # 0 = finest (first forward level)
    image_shape: Tuple[int, int]      # (H, W) consumed by the forward step
    plane_shape: Tuple[int, int]      # (H/2, W/2) polyphase planes
    fwd_steps: Tuple[PP.StepSpec, ...]
    inv_steps: Tuple[PP.StepSpec, ...]
    block: Tuple[int, int]            # resolved block edges (bh, bw)
    padded_shape: Tuple[int, int]     # plane dims padded to block multiples
    halo: int                         # halo pad per pallas_call (fuse-aware)
    # compiled tap programs, one per kernel launch group under the plan's
    # fuse mode (None when tap_opt == "off": the kernels walk raw matrices)
    fwd_programs: Optional[Tuple[C.TapProgram, ...]] = None
    inv_programs: Optional[Tuple[C.TapProgram, ...]] = None


@dataclasses.dataclass
class PyramidSpec:
    """Static execution parameters of one fused-pyramid megakernel."""

    target: Tuple[int, int]           # plane-space block target (autotuned)
    block: Tuple[int, int]            # image-space block core (bh, bw)
    padded_shape: Tuple[int, int]     # image dims padded to block multiples
    fwd_sched: C.PyramidSchedule
    inv_sched: C.PyramidSchedule
    # one whole-chain program per level (None when tap_opt == "off")
    fwd_programs: Optional[Tuple[C.TapProgram, ...]]
    inv_programs: Optional[Tuple[C.TapProgram, ...]]
    vmem_bytes: int                   # estimated VMEM footprint (max dir)

    @property
    def window_shape(self) -> Tuple[int, int]:
        m = self.fwd_sched.margins[0]
        return (self.block[0] + 2 * m, self.block[1] + 2 * m)


@dataclasses.dataclass
class DwtPlan:
    """A fully-resolved, reusable multi-level DWT executor.

    Build via :func:`build_plan` (or, preferably, through the LRU cache in
    :mod:`repro.engine.cache`), then call :meth:`execute` /
    :meth:`execute_inverse` any number of times with arrays of exactly
    ``key.shape`` / the matching pyramid.
    """

    key: PlanKey
    level_specs: Tuple[LevelSpec, ...]
    _forward: Optional[object] = None   # set by the executor module
    _inverse: Optional[object] = None
    # TileGrid when key.tiles is set (executors then come from repro.tiling)
    grid: Optional[object] = None
    # PyramidSpec for fuse="pyramid" pallas plans; None after the
    # VMEM-budget fallback (the plan then executes as fuse="levels")
    pyramid: Optional[PyramidSpec] = None
    fallback: Optional[str] = None      # why the pyramid kernel was skipped
    # AutoChoice when this plan was resolved from backend="auto"; the
    # plan's key then carries the *concrete* chosen backend/fuse/tap_opt
    auto: Optional[object] = None

    @property
    def num_steps(self) -> int:
        """Barriers per image over all levels (the paper's step count)."""
        return sum(len(ls.fwd_steps) for ls in self.level_specs)

    @property
    def backend(self) -> "B.Backend":
        """The registered :class:`~repro.engine.backends.Backend` object
        this plan executes on."""
        return B.get_backend(self.key.backend)

    @property
    def pallas_calls(self) -> int:
        """Kernel launches per execution under this plan's fuse mode, as
        modelled by the backend (:meth:`Backend.launches`): pallas_calls
        on the Pallas backend, grouped-conv calls on the XLA backend,
        zero on the jnp backend (its fuse modes only set trace
        granularity)."""
        return self.backend.launches(self)

    @property
    def tile_count(self) -> Optional[int]:
        """Tiles per execution (None for monolithic plans)."""
        return self.grid.count if self.grid is not None else None

    def compiled_stats(self) -> Optional[dict]:
        """Aggregate tap-program cost of the finest forward level (the hot
        kernel), or None when ``tap_opt == "off"``."""
        progs = self.level_specs[0].fwd_programs
        return C.program_stats(progs) if progs is not None else None

    def execute(self, x: jax.Array):
        """Forward transform of ``x`` (shape must equal ``key.shape``).

        Returns a :class:`Pyramid` (2-D), :class:`Pyramid3`
        (``key.ndim == 3``) or :class:`WaveletPacket2D`
        (``key.packet``)."""
        x = jnp.asarray(x)
        if tuple(x.shape) != self.key.shape:
            raise ValueError(
                f"plan built for shape {self.key.shape}, got {x.shape}")
        k = self.key
        EXECUTIONS.inc(op="forward", backend=k.backend, fuse=k.fuse,
                       scheme=k.scheme)
        with T.span("execute.forward", backend=k.backend, fuse=k.fuse,
                    scheme=k.scheme, levels=k.levels):
            # resilient dispatch: retry in place, then walk the
            # capability-checked degradation chain (repro.faults.degrade)
            out = R.dispatch(self, "forward", (x,))
        if k.packet is not None:
            return WaveletPacket2D(paths=k.packet, leaves=list(out))
        ll, details = out
        if k.ndim == 3:
            return Pyramid3(ll=ll, details=list(details))
        return Pyramid(ll=ll, details=list(details))

    def execute_inverse(self, pyr) -> jax.Array:
        """Inverse transform of a container produced by :meth:`execute`
        (:class:`Pyramid`, :class:`Pyramid3` or, for packet plans, a
        :class:`WaveletPacket2D` over any admissible leaf set matching
        ``key.packet``)."""
        k = self.key
        if k.packet is not None:
            if tuple(pyr.paths) != k.packet:
                raise ValueError(
                    f"plan built for packet leaves {k.packet}, "
                    f"got {tuple(pyr.paths)}")
            args = (tuple(jnp.asarray(a) for a in pyr.leaves),)
        else:
            if pyr.levels != k.levels:
                raise ValueError(
                    f"plan built for {k.levels} levels, "
                    f"pyramid has {pyr.levels}")
            args = (pyr.ll, tuple(tuple(d) for d in pyr.details))
        EXECUTIONS.inc(op="inverse", backend=k.backend, fuse=k.fuse,
                       scheme=k.scheme)
        with T.span("execute.inverse", backend=k.backend, fuse=k.fuse,
                    scheme=k.scheme, levels=k.levels):
            return R.dispatch(self, "inverse", args)


def _resolve_level(index: int, h: int, w: int, key: PlanKey,
                   fwd: Tuple[PP.StepSpec, ...],
                   inv: Tuple[PP.StepSpec, ...],
                   block_target: Tuple[int, int],
                   backend: "B.Backend") -> LevelSpec:
    hp, wp = h // 2, w // 2
    th, tw = PP.tile(coefficient_dtype(key.dtype), PP._default_interpret())
    bh, hp2 = PP._pick_block(hp, block_target[0], th)
    bw, wp2 = PP._pick_block(wp, block_target[1], tw)
    fwd_programs = inv_programs = None
    # the backend decides the tap-program compilation level (None = raw
    # matrix walk) and the fuse granularity of its *launches*: one
    # program per step (fuse="none") or one whole-chain program per
    # level (the jnp backend has no launch granularity and always runs
    # whole-chain; the xla backend lowers one conv per program).
    opt = backend.program_opt(key)
    if opt is not None:
        pfuse = backend.program_fuse(key)
        fwd_programs = C.compile_scheme_programs(
            key.wavelet, key.scheme, key.optimize, False, opt, pfuse)
        inv_programs = C.compile_scheme_programs(
            key.wavelet, key.scheme, False, True, opt, pfuse)
    if fwd_programs is not None:
        # compiled per-axis margins: never larger than the matrix halos
        halo = max(p.halo for p in fwd_programs)
    elif key.fuse == "none":
        halo = max((st.halo for st in fwd), default=0)
    else:
        halo = sum(st.halo for st in fwd)
    return LevelSpec(index=index, image_shape=(h, w), plane_shape=(hp, wp),
                     fwd_steps=fwd, inv_steps=inv, block=(bh, bw),
                     padded_shape=(hp2, wp2), halo=halo,
                     fwd_programs=fwd_programs, inv_programs=inv_programs)


def _pick_block(key: PlanKey,
                default: Tuple[int, int] = (256, 512)) -> Tuple[int, int]:
    """Block target for a plan: the autotuned table entry for this
    ``(scheme, shape, fuse, backend)`` **on this device** when one
    exists (:mod:`repro.engine.autotune`, populated by
    ``benchmarks/autotune``; the loaded table is memoized per process),
    else the static ``default``."""
    tuned = autotune.lookup(key.scheme, key.shape[-2:], key.fuse,
                            key.backend)
    return tuned if tuned is not None else default


def _resolve_pyramid(key: PlanKey, h: int, w: int,
                     block_target: Tuple[int, int]
                     ) -> Tuple[Optional[PyramidSpec], Optional[str]]:
    """Resolve the fused-pyramid megakernel spec.

    The VMEM-budget guard halves the block target until the compound
    window (double-buffered scratch + compute intermediates) fits the
    configurable limit; only when even the smallest phase-alignable
    block is over budget does the plan fall back to ``fuse="levels"``
    execution (counted in :data:`VMEM_FALLBACKS`)."""
    L = key.levels
    fwd_steps = scheme_steps(key.wavelet, key.scheme, key.optimize, False)
    inv_steps = scheme_steps(key.wavelet, key.scheme, False, True)
    fwd_programs = C.compile_pyramid_programs(
        key.wavelet, key.scheme, key.optimize, False, key.tap_opt, L)
    inv_programs = C.compile_pyramid_programs(
        key.wavelet, key.scheme, False, True, key.tap_opt, L)
    fwd_sched = C.forward_schedule(
        C.level_reaches(fwd_steps, fwd_programs, L), L)
    inv_sched = C.inverse_schedule(
        C.level_reaches(inv_steps, inv_programs, L), L)
    align = 1 << L
    itemsize = jnp.dtype(coefficient_dtype(key.dtype)).itemsize
    cdt_size = jnp.dtype(key.compute_dtype).itemsize
    limit = pyramid_vmem_limit()
    target = (int(block_target[0]), int(block_target[1]))
    floor = max(1, align // 2)      # image-space block floor = 2^levels
    spec = None
    while True:
        bh, hp2 = PP._pick_block_aligned(h, 2 * target[0], align)
        bw, wp2 = PP._pick_block_aligned(w, 2 * target[1], align)
        m = fwd_sched.margins[0]
        fwd_wins = [(bh + 2 * m, bw + 2 * m)]
        in_margins = [inv_sched.margins[L]] + \
            [inv_sched.margins[l + 1]
             for l in PP.pyramid_out_levels(L)[1:]]
        inv_wins = [((bh >> (l + 1)) + 2 * g, (bw >> (l + 1)) + 2 * g)
                    for l, g in zip(PP.pyramid_out_levels(L), in_margins)]
        vmem = max(PP.pyramid_vmem_bytes(L, fwd_wins, itemsize, cdt_size),
                   PP.pyramid_vmem_bytes(L, inv_wins, itemsize, cdt_size))
        spec = PyramidSpec(target=target, block=(bh, bw),
                           padded_shape=(hp2, wp2),
                           fwd_sched=fwd_sched, inv_sched=inv_sched,
                           fwd_programs=fwd_programs,
                           inv_programs=inv_programs, vmem_bytes=vmem)
        if vmem <= limit:
            return spec, None
        smaller = (max(target[0] // 2, floor), max(target[1] // 2, floor))
        if smaller == target:
            break
        target = smaller
    VMEM_FALLBACKS.inc()
    return None, (f"pyramid window {spec.window_shape} needs "
                  f"~{spec.vmem_bytes} B VMEM > limit {limit} B even at "
                  f"the minimum block; executing as fuse='levels'")


def build_plan(key: PlanKey,
               block_target: Optional[Tuple[int, int]] = None) -> DwtPlan:
    """Resolve a :class:`PlanKey` into an executable :class:`DwtPlan`.

    ``block_target`` ``None`` consults the autotuned block table
    (:func:`_pick_block`) and falls back to the static ``(256, 512)``;
    an explicit value skips the table (the autotuner itself uses this).

    Backend dispatch goes through the registry
    (:mod:`repro.engine.backends`): unknown backends and unsupported
    ``(backend, PlanKey)`` combinations raise
    :class:`~repro.engine.backends.BackendError` here, at plan build,
    with the offending PlanKey field named.

    ``backend="auto"`` delegates to the profiler
    (:func:`repro.profiler.auto.choose`): the measured cost model picks
    the concrete ``(backend, fuse, block_target, tap_opt)`` for this
    device, and the returned plan — bit-identical in output to a manual
    build of that configuration — carries the chosen backend in its key
    plus the :class:`~repro.profiler.auto.AutoChoice` on ``plan.auto``.
    """
    with T.span("plan.build", backend=key.backend, fuse=key.fuse,
                scheme=key.scheme, levels=key.levels):
        FI.maybe_inject("plan.build", backend=key.backend, fuse=key.fuse)
        return _build_plan(key, block_target)


def _build_plan(key: PlanKey,
                block_target: Optional[Tuple[int, int]] = None) -> DwtPlan:
    PLAN_BUILDS.inc(backend=key.backend, fuse=key.fuse, scheme=key.scheme)
    backend = B.get_backend(key.backend)
    if key.fuse not in FUSE_MODES:
        raise ValueError(f"unknown fuse mode {key.fuse!r}; "
                         f"available: {FUSE_MODES}")
    if key.boundary not in BOUNDARIES:
        raise ValueError(f"unknown boundary {key.boundary!r}; "
                         f"available: {BOUNDARIES}")
    if key.compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(f"unknown compute_dtype {key.compute_dtype!r}; "
                         f"available: {COMPUTE_DTYPES}")
    if key.tap_opt not in C.OPT_LEVELS:
        raise ValueError(f"unknown tap_opt {key.tap_opt!r}; "
                         f"available: {C.OPT_LEVELS}")
    if key.levels < 1:
        raise ValueError(f"levels must be >= 1, got {key.levels}")
    validate_samples(key)
    demoted = None
    if key.ndim not in (2, 3):
        raise ValueError(f"ndim must be 2 or 3, got {key.ndim}")
    if key.packet is not None or key.ndim == 3:
        workload = "packet" if key.packet is not None else "dwt3"
        if key.packet is not None and key.ndim != 2:
            raise ValueError(
                "packet transforms are 2-D (PlanKey.packet with "
                f"ndim={key.ndim}); decompose frames individually or "
                "use the plain 3-D pyramid (ndim=3, packet=None)")
        if key.tiles is not None:
            raise ValueError(
                f"tiled execution (PlanKey.tiles={key.tiles!r}) is "
                f"2-D-pyramid-only; {workload} plans run monolithic")
        if key.packet is not None:
            from repro.core import packets as PK
            tree = PK.PacketTree(key.packet)   # validates admissibility
            if tree.depth != key.levels:
                raise ValueError(
                    f"PlanKey.levels={key.levels} must equal the packet "
                    f"tree depth {tree.depth} (get_plan normalizes this)")
        if key.fuse == "pyramid":
            # capability-checked demotion: the megakernel fuses the 2-D
            # LL recursion only — packet trees branch into all four
            # children and the 3-D level interleaves a temporal pass
            WORKLOAD_DEMOTIONS.inc(workload=workload, backend=key.backend)
            key = dataclasses.replace(key, fuse="levels")
            demoted = (f"fuse='pyramid' is the 2-D pyramid megakernel; "
                       f"{workload} plan executes as fuse='levels'")
    min_rank = 3 if key.ndim == 3 else 2
    want = "(..., T, H, W)" if key.ndim == 3 else "(..., H, W)"
    if len(key.shape) < min_rank:
        raise ValueError(f"input must be {want}, got {key.shape}")
    backend.validate(key)
    h, w = key.shape[-2], key.shape[-1]
    validate_image_geometry(h, w, key.levels)
    if key.ndim == 3:
        t, div = key.shape[-3], 1 << key.levels
        if t % div:
            raise ValueError(
                f"levels={key.levels} infeasible for volume "
                f"{t}x{h}x{w}: T={t} is not divisible by "
                f"2^levels={div}")

    if key.backend == "auto":
        # profile-guided resolution: the cost model (or the cold-start
        # heuristic) picks the concrete (backend, fuse, block, tap_opt);
        # the returned plan executes — bit-identically — on the chosen
        # backend, and records the choice for engine.stats()
        from repro.profiler import auto as PA  # deferred: profiler->engine
        choice = PA.choose(key, block_target=block_target)
        concrete = dataclasses.replace(key, backend=choice.backend,
                                       fuse=choice.fuse,
                                       tap_opt=choice.tap_opt)
        plan = build_plan(concrete,
                          block_target=(block_target if block_target
                                        is not None else choice.block))
        plan.auto = choice
        return plan

    if block_target is None:
        block_target = _pick_block(key)

    fwd = scheme_steps(key.wavelet, key.scheme, key.optimize, False)
    inv = scheme_steps(key.wavelet, key.scheme, False, True)
    specs = []
    for lvl in range(key.levels):
        specs.append(_resolve_level(lvl, h >> lvl, w >> lvl, key, fwd, inv,
                                    block_target, backend))
    plan = DwtPlan(key=key, level_specs=tuple(specs))
    if demoted is not None:
        plan.fallback = demoted
    if key.packet is not None:
        from repro.engine import executor as X
        plan._forward = X.make_packet_forward(plan, backend)
        plan._inverse = X.make_packet_inverse(plan, backend)
        return plan
    if key.ndim == 3:
        from repro.engine import executor as X
        if key.fuse == "levels" and not backend.temporal_fuse \
                and plan.fallback is None:
            plan.fallback = (
                f"backend {key.backend!r} has no fused t+2D trace; the "
                f"temporal pass runs unfused between its 2-D levels")
        plan._forward = X.make_dwt3_forward(plan, backend)
        plan._inverse = X.make_dwt3_inverse(plan, backend)
        return plan
    if key.fuse == "pyramid" and backend.pyramid_kernel \
            and key.tiles is None:
        plan.pyramid, plan.fallback = _resolve_pyramid(key, h, w,
                                                       block_target)

    if key.tiles is not None:
        # deferred: tiling sits above the engine and imports it back
        from repro.tiling import api as TA
        from repro.tiling import grid as TG
        plan.grid = TG.build_grid((h, w), key.tiles, key.levels, specs)

        def _lazy(make):
            # tiled executors build on first use: a plan fetched only for
            # its grid geometry (e.g. stream_dwt2, the shard_map
            # transport) never builds the gather window plans behind them
            slot = []

            def call(*args):
                if not slot:
                    slot.append(make(plan))
                return slot[0](*args)
            return call

        plan._forward = _lazy(TA.make_tiled_forward)
        plan._inverse = _lazy(TA.make_tiled_inverse)
        return plan

    plan._forward = backend.make_forward(plan)
    plan._inverse = backend.make_inverse(plan)
    return plan
