"""Per-level executor arithmetic: *how one pyramid level runs* on each
registered backend.

This module holds the level-granularity building blocks — polyphase
split/merge plus a StepSpec walk or compiled-tap-program run — that the
backend objects in :mod:`repro.engine.backends` assemble into full plan
executors.  The split of responsibilities:

* ``executor.py``  (here)  — level arithmetic: image -> 4 subband planes
  (and back) for the jnp roll path, the Pallas window kernels, and the
  XLA grouped-conv path, plus the fused-pyramid megakernel wrappers;
* ``backends.py``          — dispatch policy: which fuse modes a backend
  supports, how levels chain, what gets jitted, how launches are
  counted.

All level functions accept batched ``(..., H, W)`` input: the jnp and
conv paths broadcast over leading dims, the Pallas kernels flatten them
into the leading grid dimension of the ``pallas_call``.
"""
from __future__ import annotations

import time
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import schemes as S
from repro.kernels import polyphase as PP
from repro.compiler import conv as CV
from repro.compiler import execute as CX
from repro import telemetry as T
from repro.faults import degrade as DG
from repro.faults import inject as FI


COMPILES = T.counter(
    "repro_executor_compiles_total",
    "plan-executor compiles (lower + compile, persistent-cache loads "
    "included), by executable", labelnames=("op",))
COMPILE_SECONDS = T.counter(
    "repro_executor_compile_seconds_total",
    "host seconds in plan-executor lower + compile, by executable",
    labelnames=("op",))


def compiled_jit(fn):
    """``jax.jit(fn)`` whose lowering and compilation are checked apart
    from its execution.

    The first call with each argument signature lowers and compiles
    ahead of time; a failure there raises
    :class:`~repro.faults.degrade.KernelCompileError`, which the
    resilient dispatch propagates as a defect instead of serving the
    plan on another backend.  The call itself then reuses that
    executable (JAX shares the compilation between ``lower().compile()``
    and the call).  Called under an outer trace, it traces inline.

    The executable is named after ``fn`` (``jit_dwt_forward``).  Each
    compile is a ``plan.compile`` span and counts in
    ``repro_executor_compiles_total`` / ``_compile_seconds_total``
    (label ``op``: ``fn``'s name); under ``spans`` it also records the
    executable's op-to-scope map (:func:`repro.telemetry.op_scopes`).
    Later calls do none of this.
    """
    name = fn.__name__
    jitted = jax.jit(fn)
    checked = set()

    def compile_once(args):
        t0 = time.perf_counter()
        with T.span("plan.compile", op=name):
            try:
                compiled = jitted.lower(*args).compile()
            except FI.InjectedFault:
                raise
            except Exception as e:
                raise DG.KernelCompileError(
                    f"plan executor failed to lower or compile: "
                    f"{type(e).__name__}: {e}") from e
        COMPILES.inc(op=name)
        COMPILE_SECONDS.inc(time.perf_counter() - t0, op=name)
        if T.CONFIG.spans_on:
            T.record_op_scopes(compiled.as_text())

    def call(*args):
        leaves, tree = jax.tree_util.tree_flatten(args)
        if not any(isinstance(a, jax.core.Tracer) for a in leaves):
            sig = (tree, tuple((jnp.shape(a), jnp.result_type(a),
                                getattr(a, "sharding", None))
                               for a in leaves))
            if sig not in checked:
                compile_once(args)
                checked.add(sig)
        return jitted(*args)

    return call


def apply_steps_jnp(steps: Sequence[PP.StepSpec], planes: S.Planes
                    ) -> S.Planes:
    """Run a StepSpec sequence on polyphase planes with the jnp reference
    kernels (handles raw and Section-5-optimized step triples alike)."""
    for st in steps:
        for m in st.pre:
            planes = S.apply_matrix(m, planes)
        if st.main is not None:
            planes = S.apply_matrix(st.main, planes)
        for m in st.post:
            planes = S.apply_matrix(m, planes)
    return planes


def run_programs_jnp(programs, planes, compute_dtype):
    """Execute compiled tap programs on full planes (periodic rolls),
    computing in ``compute_dtype`` and casting back to the I/O dtype."""
    out_dtype = planes[0].dtype
    cur = [p.astype(compute_dtype) for p in planes]
    for prog in programs:
        cur = CX.run_planes(prog, cur)
    return tuple(p.astype(out_dtype) for p in cur)


def samples_in(x, key):
    """The forward's input as the transform sees it: integer samples
    level-shifted (``polyphase.level_shift``), float input as it is."""
    if not jnp.issubdtype(x.dtype, jnp.integer):
        return x
    return PP.level_shift(x, key.bit_depth, jnp.dtype(key.compute_dtype))


def slices_to_planes(x, spec, key):
    """The polyphase split of the jnp and xla forwards: strided slices,
    after the level shift of integer samples."""
    x = samples_in(x, key)
    PP.count_split(x, "slices", spec.index)
    return S.to_planes(x)


# ---------------------------------------------------------------------------
# jnp backend: periodic rolls over whole planes
# ---------------------------------------------------------------------------

def jnp_level_forward(x, spec, key):
    """One forward level: image (..., H, W) -> 4 planes (..., H/2, W/2)."""
    planes = slices_to_planes(x, spec, key)
    cdt = jnp.dtype(key.compute_dtype)
    if spec.fwd_programs is not None:
        return run_programs_jnp(spec.fwd_programs, planes, cdt)
    out_dtype = planes[0].dtype
    planes = tuple(p.astype(cdt) for p in planes)
    return tuple(p.astype(out_dtype)
                 for p in apply_steps_jnp(spec.fwd_steps, planes))


def jnp_level_inverse(planes, spec, key):
    """One inverse level: 4 subband planes -> image (..., H, W)."""
    cdt = jnp.dtype(key.compute_dtype)
    if spec.inv_programs is not None:
        planes = run_programs_jnp(spec.inv_programs, planes, cdt)
    else:
        out_dtype = planes[0].dtype
        planes = tuple(p.astype(cdt) for p in planes)
        planes = tuple(p.astype(out_dtype)
                       for p in apply_steps_jnp(spec.inv_steps, planes))
    return S.from_planes(planes)


# ---------------------------------------------------------------------------
# pallas backend: VMEM window kernels
# ---------------------------------------------------------------------------

def pallas_level_forward(x, spec, key):
    bit_depth = key.bit_depth if jnp.issubdtype(x.dtype, jnp.integer) \
        else None
    planes = PP.to_planes(x, bit_depth, jnp.dtype(key.compute_dtype),
                          level=spec.index)
    return PP.apply_steps_pallas(
        spec.fwd_steps, planes,
        fuse=("none" if key.fuse == "none" else "scheme"),
        block=spec.block, compute_dtype=jnp.dtype(key.compute_dtype),
        tap_opt=key.tap_opt, programs=spec.fwd_programs,
        level=spec.index)


def pallas_level_inverse(planes, spec, key):
    planes = PP.apply_steps_pallas(
        spec.inv_steps, planes,
        fuse=("none" if key.fuse == "none" else "scheme"),
        block=spec.block, compute_dtype=jnp.dtype(key.compute_dtype),
        tap_opt=key.tap_opt, programs=spec.inv_programs,
        level=spec.index, inverse=True)
    return S.from_planes(planes)


# ---------------------------------------------------------------------------
# xla backend: grouped lax.conv_general_dilated over the polyphase planes
# ---------------------------------------------------------------------------

def xla_level_forward(x, spec, key):
    planes = slices_to_planes(x, spec, key)
    return CV.run_planes_conv(spec.fwd_programs, planes,
                              jnp.dtype(key.compute_dtype))


def xla_level_inverse(planes, spec, key):
    planes = CV.run_planes_conv(spec.inv_programs, planes,
                                jnp.dtype(key.compute_dtype))
    return S.from_planes(planes)


# ---------------------------------------------------------------------------
# wavelet packets + 3-D (t+2D): generic executors over the level hooks
# ---------------------------------------------------------------------------
#
# Both workloads compose the per-level hooks every backend already
# implements (``level_forward`` / ``level_inverse``), so they run on all
# registered backends with no backend-specific kernel work: a packet
# node at depth d has exactly the geometry of pyramid level d (the
# plan's LevelSpecs are reused by depth), and the 3-D transform's
# temporal half-bands ride the free leading batch dims of the 2-D
# kernels.  ``fuse="levels"`` traces the whole tree/pyramid once on
# backends whose capability flags allow it (``temporal_fuse`` gates the
# fused t+2D trace; pallas keeps the temporal pass unfused).


def _fuse_trace(plan, backend, run):
    """Shared jit policy of the packet/3-D executors: one whole-tree
    trace under fuse="levels" when the backend allows it, else the
    eager per-node chain."""
    if plan.key.fuse == "levels" and backend.temporal_fuse:
        return compiled_jit(run)
    return run


def make_packet_forward(plan, backend):
    """Forward packet executor: image -> leaf arrays in canonical order
    (a tuple, so the resilience plane's verification walks it like any
    other plane list)."""
    from repro.core import packets as PK
    key, specs = plan.key, plan.level_specs
    tree = PK.PacketTree(key.packet)
    internal, leaves = tree.internal_nodes(), tree.leaves

    def packet_forward(x):
        nodes = {"": x}
        for path in internal:
            spec = specs[len(path)]
            with T.span("packet.forward", depth=len(path),
                        backend=backend.name):
                children = backend.level_forward(nodes.pop(path), spec, key)
            for c, arr in zip(PK.CHILDREN, children):
                nodes[path + c] = arr
        return tuple(nodes[p] for p in leaves)

    return _fuse_trace(plan, backend, packet_forward)


def make_packet_inverse(plan, backend):
    """Inverse packet executor: canonical leaf tuple -> image, walking
    the internal nodes bottom-up (exact reconstruction from any
    admissible leaf set)."""
    from repro.core import packets as PK
    key, specs = plan.key, plan.level_specs
    tree = PK.PacketTree(key.packet)
    internal, leaves = tree.internal_nodes(), tree.leaves

    def packet_inverse(leaf_arrays):
        nodes = dict(zip(leaves, leaf_arrays))
        for path in reversed(internal):
            spec = specs[len(path)]
            children = tuple(nodes.pop(path + c) for c in PK.CHILDREN)
            with T.span("packet.inverse", depth=len(path),
                        backend=backend.name):
                nodes[path] = backend.level_inverse(children, spec, key)
        return nodes[""]

    return _fuse_trace(plan, backend, packet_inverse)


def make_dwt3_forward(plan, backend):
    """Forward 3-D executor: volume (..., T, H, W) -> (lll, details
    coarsest-first).  Each level lifts along time (periodic 1-D lifting,
    :mod:`repro.compiler.temporal`) then transforms both temporal
    half-bands with the backend's compiled 2-D level; only the tL·LL
    subband recurses."""
    from repro.compiler import temporal as TP
    key, specs = plan.key, plan.level_specs
    prog = TP.compile_temporal(key.wavelet)
    cdt = jnp.dtype(key.compute_dtype)

    def dwt3_forward(x):
        details = []
        v = x
        for spec in specs:
            with T.span("level3.forward", level=spec.index,
                        backend=backend.name):
                lo, hi = TP.temporal_forward(v, prog, cdt)
                v, hl0, lh0, hh0 = backend.level_forward(lo, spec, key)
                llh, hlh, lhh, hhh = backend.level_forward(hi, spec, key)
            details.append((hl0, lh0, hh0, llh, hlh, lhh, hhh))
        return v, tuple(details[::-1])

    return _fuse_trace(plan, backend, dwt3_forward)


def make_dwt3_inverse(plan, backend):
    """Inverse 3-D executor: (lll, details coarsest-first) -> volume."""
    from repro.compiler import temporal as TP
    key, specs = plan.key, plan.level_specs
    prog = TP.compile_temporal(key.wavelet, inverse=True)
    cdt = jnp.dtype(key.compute_dtype)

    def dwt3_inverse(ll, details):
        v = ll
        for spec, det in zip(reversed(specs), details):
            hl0, lh0, hh0, llh, hlh, lhh, hhh = det
            with T.span("level3.inverse", level=spec.index,
                        backend=backend.name):
                lo = backend.level_inverse((v, hl0, lh0, hh0), spec, key)
                hi = backend.level_inverse((llh, hlh, lhh, hhh), spec, key)
                v = TP.temporal_inverse(lo, hi, prog, cdt)
        return v

    return _fuse_trace(plan, backend, dwt3_inverse)


# ---------------------------------------------------------------------------
# fused-pyramid megakernel (pallas only)
# ---------------------------------------------------------------------------

def _pyramid_kernel_kwargs(plan, inverse: bool) -> dict:
    key, spec = plan.key, plan.pyramid
    steps = (plan.level_specs[0].inv_steps if inverse
             else plan.level_specs[0].fwd_steps)
    return dict(
        levels=key.levels, steps=steps,
        sched=spec.inv_sched if inverse else spec.fwd_sched,
        programs=spec.inv_programs if inverse else spec.fwd_programs,
        # the plane-space target; the kernel re-derives the image-space
        # block exactly like _resolve_pyramid did (single source: the
        # shared _pick_block_aligned walk)
        block=spec.target,
        compute_dtype=jnp.dtype(key.compute_dtype))


def make_pyramid_forward(plan):
    """Forward executor of a fused-pyramid plan: one pallas_call for the
    whole multi-level transform (details returned coarsest-first)."""
    from repro.engine import plan as PLAN
    levels = plan.key.levels
    scheme = plan.key.scheme
    kw = _pyramid_kernel_kwargs(plan, False)

    def dwt_forward(x):
        return PP.pyramid_forward_pallas(samples_in(x, plan.key), **kw)

    fn = compiled_jit(dwt_forward)

    def run(x):
        PLAN.PYRAMID_LAUNCHES.inc()
        FI.maybe_inject("pyramid.launch", op="forward", scheme=scheme)
        with T.span("pyramid.launch", op="forward", levels=levels,
                    scheme=scheme):
            ll, details = fn(x)
        return ll, tuple(details[::-1])

    return run


def make_pyramid_inverse(plan):
    """Inverse executor of a fused-pyramid plan (single pallas_call)."""
    from repro.engine import plan as PLAN
    levels = plan.key.levels
    scheme = plan.key.scheme
    kw = _pyramid_kernel_kwargs(plan, True)

    def dwt_inverse(ll, details):
        return PP.pyramid_inverse_pallas(ll, details, **kw)

    fn = compiled_jit(dwt_inverse)

    def run(ll, details):
        PLAN.PYRAMID_LAUNCHES.inc()
        FI.maybe_inject("pyramid.launch", op="inverse", scheme=scheme)
        with T.span("pyramid.launch", op="inverse", levels=levels,
                    scheme=scheme):
            return fn(ll, tuple(details[::-1]))

    return run
