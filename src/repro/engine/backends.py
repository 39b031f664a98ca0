"""Pluggable backend registry: the engine's single executor-dispatch point.

Until PR 5, executor selection was hard-wired ``if backend == ...``
branches threaded through the engine.  This module replaces them with a
registry of :class:`Backend` objects.  Each backend declares

* **capabilities** — supported fuse modes, compute dtypes, whether tiled
  plans and the fused-pyramid megakernel exist on it;
* a **plan-compatibility check** (:meth:`Backend.validate`) that runs at
  plan build, so an unsupported ``(backend, PlanKey)`` combination fails
  with an actionable error naming the offending PlanKey field instead of
  erroring deep inside kernel tracing;
* the **executor factories** (:meth:`Backend.make_forward` /
  :meth:`Backend.make_inverse`) the plan layer installs as
  ``plan._forward`` / ``plan._inverse``, plus :meth:`Backend.execute`
  / :meth:`Backend.execute_inverse` convenience entry points;
* a **launch model** (:meth:`Backend.launches`) — kernel launches per
  execution, what ``DwtPlan.pallas_calls`` and the benchmarks report.

Registered backends:

* ``"jnp"``    — pure-jnp reference: periodic rolls over whole planes,
  broadcasts over batch dims; the numerics oracle.
* ``"pallas"`` — Pallas window kernels: compiled by Mosaic on a TPU,
  run by the Pallas interpreter on the CPU (tests), refused on any other
  platform.  The ``fuse="pyramid"`` megakernel runs on the CPU only:
  Mosaic does not lower its in-VMEM polyphase split, so a TPU plan
  build rejects it.
* ``"xla"``    — compiled tap programs lowered to grouped
  ``lax.conv_general_dilated`` calls over the polyphase planes
  (:mod:`repro.compiler.conv`): one fused conv per step, batched,
  GPU/TPU/CPU-portable with no Pallas dependency.  This is the path
  that runs fast on GPUs today — XLA hands the composed filter banks to
  the vendor conv libraries of both biggest GPU vendors.
* ``"auto"``   — profile-guided meta-backend: plan build asks the
  measured cost model (:mod:`repro.profiler`) to pick the concrete
  ``(backend, fuse, block_target, tap_opt)`` for this key on this
  device, falling back to a deterministic platform heuristic when the
  trace store is cold.  Plans never execute on it directly — the
  resolved plan carries the chosen concrete backend.

Third-party backends register the same way the built-ins do::

    from repro.engine import backends

    class MyBackend(backends.Backend):
        name = "mine"
        ...

    backends.register_backend(MyBackend())
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax

from repro import telemetry as T
from repro.engine import executor as X

__all__ = ["Backend", "BackendError", "register_backend", "get_backend",
           "available_backends", "capability_matrix"]


class BackendError(ValueError):
    """An unknown backend, or a ``(backend, PlanKey)`` combination the
    backend cannot execute.  Raised at plan build, before any tracing,
    with the offending PlanKey field named."""


class Backend:
    """One execution strategy for compiled DWT plans.

    Subclasses override the class attributes to declare capabilities and
    the ``level_forward`` / ``level_inverse`` hooks (or all of
    ``make_forward`` / ``make_inverse``) to define execution.  The base
    class provides the generic level-chaining executor and the shared
    fuse-mode jit policy: ``fuse="levels"`` traces the whole pyramid
    once; ``fuse="pyramid"`` defers to :meth:`_pyramid_forward` /
    :meth:`_pyramid_inverse`; other modes chain eagerly (optionally with
    one jitted call per level, see ``jit_per_level``).
    """

    name: str = "?"
    description: str = ""
    #: fuse modes this backend can execute (PlanKey.fuse)
    fuse_modes: Tuple[str, ...] = ("none", "scheme", "levels", "pyramid")
    #: in-kernel arithmetic dtypes (PlanKey.compute_dtype)
    compute_dtypes: Tuple[str, ...] = ("float32", "bfloat16")
    #: whether tiled plans (PlanKey.tiles) may run through this backend
    supports_tiles: bool = True
    #: True when fuse="pyramid" is a real single-launch megakernel (not
    #: just a trace-granularity alias)
    pyramid_kernel: bool = False
    #: wrap each level's dispatch in its own jax.jit under
    #: fuse="none"/"scheme" (kernel backends want this; jnp stays eager)
    jit_per_level: bool = False
    #: whether packet plans (PlanKey.packet) may run through this backend
    supports_packets: bool = True
    #: whether 3-D (t+2D) plans (PlanKey.ndim == 3) may run through it
    supports_3d: bool = True
    #: True when the t+2D level (temporal lifting + both 2-D half-band
    #: transforms) fuses into one trace under fuse="levels"; False keeps
    #: the temporal pass unfused (the pallas capability fallback: its
    #: window kernels dispatch per level, the jnp temporal pass runs
    #: between them)
    temporal_fuse: bool = True

    # -- plan-build hooks --------------------------------------------------

    def validate(self, key) -> None:
        """Reject PlanKeys this backend cannot execute (actionable: the
        message names the offending PlanKey field and the supported
        values).  Generic value errors (unknown fuse mode, bad levels,
        geometry) are raised by ``build_plan`` before this runs."""
        if key.fuse not in self.fuse_modes:
            raise BackendError(
                f"backend {self.name!r} does not support "
                f"PlanKey.fuse={key.fuse!r}; fuse modes supported by "
                f"{self.name!r}: {self.fuse_modes}")
        if key.compute_dtype not in self.compute_dtypes:
            raise BackendError(
                f"backend {self.name!r} does not support "
                f"PlanKey.compute_dtype={key.compute_dtype!r}; compute "
                f"dtypes supported by {self.name!r}: {self.compute_dtypes}")
        if key.tiles is not None and not self.supports_tiles:
            raise BackendError(
                f"backend {self.name!r} does not support tiled plans "
                f"(PlanKey.tiles={key.tiles!r})")
        packet = getattr(key, "packet", None)
        ndim = getattr(key, "ndim", 2)
        if packet is not None and not self.supports_packets:
            raise BackendError(
                f"backend {self.name!r} does not support wavelet-packet "
                f"plans (PlanKey.packet={packet!r})")
        if ndim == 3 and not self.supports_3d:
            raise BackendError(
                f"backend {self.name!r} does not support 3-D plans "
                f"(PlanKey.ndim=3)")
        if (packet is not None or ndim == 3) and key.fuse == "pyramid":
            # keeps pyramid out of the profiler's candidate set and the
            # degradation chain for these workloads; build_plan demotes
            # user-passed fuse="pyramid" before this check runs
            raise BackendError(
                f"fuse='pyramid' is the 2-D pyramid megakernel; packet "
                f"and 3-D plans on {self.name!r} execute at "
                f"fuse='levels' (build_plan demotes automatically)")

    def program_opt(self, key) -> Optional[str]:
        """Tap-program compilation level for this backend, or None when
        the backend executes the raw matrix walk (``tap_opt="off"``)."""
        return None if key.tap_opt == "off" else key.tap_opt

    def program_fuse(self, key) -> str:
        """Granularity of the compiled programs: ``"none"`` = one program
        per barrier step, anything else = one whole-chain program per
        level.  Default: follow the plan's launch granularity."""
        return key.fuse

    # -- execution ---------------------------------------------------------

    def level_forward(self, x, spec, key):
        """One forward level: image (..., H, W) -> 4 subband planes."""
        raise NotImplementedError

    def level_inverse(self, planes, spec, key):
        """One inverse level: 4 subband planes -> image (..., H, W)."""
        raise NotImplementedError

    def make_forward(self, plan):
        """Build the forward executor: x -> (ll, details coarsest-first).
        Jitted, it is the executable ``dwt_forward``; each level's ops
        carry the scope ``dwt.level<i>``."""
        key, specs = plan.key, plan.level_specs

        def dwt_forward(x):
            details = []
            ll = x
            for spec in specs:
                # spans no-op while jax traces (fuse="levels"/"pyramid");
                # eager chains get one timed span per level
                with T.span("level.forward", level=spec.index,
                            backend=self.name), \
                        jax.named_scope(f"dwt.level{spec.index}"):
                    ll, hl, lh, hh = self.level_forward(ll, spec, key)
                details.append((hl, lh, hh))
            return ll, tuple(details[::-1])

        if key.fuse == "pyramid":
            return self._pyramid_forward(plan, dwt_forward)
        if key.fuse == "levels":
            # one trace for the whole pyramid: levels chain without
            # returning to Python between them
            return X.compiled_jit(dwt_forward)
        if self.jit_per_level:
            # seed-granularity dispatch (one jitted call per level), but
            # with plan-resolved steps/blocks instead of per-call rebuilds
            fns = [self._jit_level(self.level_forward, spec, key,
                                   "dwt_forward")
                   for spec in specs]

            def run_jit(x):
                details = []
                ll = x
                for lvl, fn in enumerate(fns):
                    with T.span("level.forward", level=lvl,
                                backend=self.name):
                        ll, hl, lh, hh = fn(ll)
                    details.append((hl, lh, hh))
                return ll, tuple(details[::-1])

            return run_jit
        return dwt_forward

    def make_inverse(self, plan):
        """Build the inverse executor: (ll, details coarsest-first) -> x;
        jitted, the executable ``dwt_inverse``."""
        key, specs = plan.key, plan.level_specs

        def dwt_inverse(ll, details):
            for spec, (hl, lh, hh) in zip(reversed(specs), details):
                with T.span("level.inverse", level=spec.index,
                            backend=self.name), \
                        jax.named_scope(f"dwt.level{spec.index}"):
                    ll = self.level_inverse((ll, hl, lh, hh), spec, key)
            return ll

        if key.fuse == "pyramid":
            return self._pyramid_inverse(plan, dwt_inverse)
        if key.fuse == "levels":
            return X.compiled_jit(dwt_inverse)
        if self.jit_per_level:
            fns = [self._jit_level(self.level_inverse, spec, key,
                                   "dwt_inverse")
                   for spec in specs]

            def run_jit(ll, details):
                for lvl, (fn, (hl, lh, hh)) in enumerate(
                        zip(reversed(fns), details)):
                    with T.span("level.inverse", level=lvl,
                                backend=self.name):
                        ll = fn((ll, hl, lh, hh))
                return ll

            return run_jit
        return dwt_inverse

    @staticmethod
    def _jit_level(level_fn, spec, key, name: str):
        """One level as its own executable, ``<name>_l<i>``."""
        def level(v):
            with jax.named_scope(f"dwt.level{spec.index}"):
                return level_fn(v, spec, key)

        level.__name__ = f"{name}_l{spec.index}"
        return X.compiled_jit(level)

    def _pyramid_forward(self, plan, run):
        """fuse="pyramid" policy for backends without a megakernel:
        execute as fuse="levels" (single trace)."""
        return X.compiled_jit(run)

    def _pyramid_inverse(self, plan, run):
        return X.compiled_jit(run)

    def execute(self, plan, x):
        """Registry-level entry point: run ``plan`` forward on ``x``.
        The plan must have been built for this backend (plans embed
        their executors at build time)."""
        self._check_plan(plan)
        return plan.execute(x)

    def execute_inverse(self, plan, pyr):
        self._check_plan(plan)
        return plan.execute_inverse(pyr)

    def _check_plan(self, plan) -> None:
        if plan.key.backend != self.name:
            raise BackendError(
                f"plan was built for backend {plan.key.backend!r}, not "
                f"{self.name!r}; rebuild it with backend={self.name!r}")

    # -- observability -----------------------------------------------------

    def launches(self, plan) -> int:
        """Kernel launches per execution under this plan (0 = the backend
        launches no kernels; its fuse modes only set trace granularity)."""
        return 0

    def capabilities(self) -> dict:
        return {"backend": self.name, "fuse_modes": self.fuse_modes,
                "compute_dtypes": self.compute_dtypes,
                "tiles": self.supports_tiles,
                "pyramid_kernel": self.pyramid_kernel,
                "packets": self.supports_packets,
                "supports_3d": self.supports_3d,
                "temporal_fuse": self.temporal_fuse,
                "description": self.description}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Backend] = {}


def register_backend(backend: Backend, replace: bool = False) -> Backend:
    """Register a backend under ``backend.name``; re-registration needs
    ``replace=True`` (so tests can swap instrumented doubles in)."""
    if backend.name in _REGISTRY and not replace:
        raise ValueError(f"backend {backend.name!r} already registered; "
                         f"pass replace=True to override")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    """Resolve a backend by name; unknown names raise an actionable
    :class:`BackendError` listing every registered backend."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise BackendError(
            f"unknown backend {name!r} (PlanKey.backend); registered "
            f"backends: {available_backends()}") from None


def available_backends() -> Tuple[str, ...]:
    """Names of all registered backends, sorted."""
    return tuple(sorted(_REGISTRY))


def capability_matrix() -> Tuple[dict, ...]:
    """One capability row per registered backend (for stats/benchmarks)."""
    return tuple(_REGISTRY[n].capabilities() for n in available_backends())


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

class JnpBackend(Backend):
    """Pure-jnp reference: periodic rolls over whole (batched) planes.

    No kernels are launched; fuse modes only set trace granularity, and
    ``fuse="pyramid"`` runs the eager per-level chain (bit-identical to
    ``fuse="none"`` — there is no kernel granularity to fuse)."""

    name = "jnp"
    description = "pure-jnp reference (roll-based periodic convolution)"

    def program_fuse(self, key) -> str:
        # no launch granularity: always run one whole-chain program/level
        return "scheme"

    def level_forward(self, x, spec, key):
        return X.jnp_level_forward(x, spec, key)

    def level_inverse(self, planes, spec, key):
        return X.jnp_level_inverse(planes, spec, key)

    def _pyramid_forward(self, plan, run):
        return run     # eager chain, bit-identical to fuse="none"

    def _pyramid_inverse(self, plan, run):
        return run


class PallasBackend(Backend):
    """Pallas window kernels: batch rides the leading grid dimension,
    tile-aligned VMEM halo windows via double-buffered DMA.  Mosaic
    compiles them on a TPU; on the CPU they run in the Pallas
    interpreter (the test path).  ``fuse="pyramid"`` is the single-call
    megakernel, which Mosaic does not lower yet: a TPU plan build
    rejects it."""

    name = "pallas"
    description = ("Pallas window kernels (Mosaic on TPU, interpreter on "
                   "CPU)")
    pyramid_kernel = True
    jit_per_level = True
    # capability-checked 3-D fallback: the window kernels dispatch per
    # level, so the jnp temporal pass runs unfused between them
    temporal_fuse = False

    def validate(self, key) -> None:
        super().validate(key)
        platform = jax.default_backend()
        if platform not in ("cpu", "tpu"):
            raise BackendError(
                f"PlanKey.backend='pallas' runs Mosaic kernels on a TPU "
                f"and the Pallas interpreter on the CPU; platform "
                f"{platform!r} has neither (use backend='xla')")
        if key.fuse == "pyramid" and platform == "tpu":
            raise BackendError(
                "PlanKey.fuse='pyramid' does not compile for the TPU: "
                "Mosaic does not lower the megakernel's in-VMEM "
                "polyphase split; use fuse='levels'")

    def level_forward(self, x, spec, key):
        return X.pallas_level_forward(x, spec, key)

    def level_inverse(self, planes, spec, key):
        return X.pallas_level_inverse(planes, spec, key)

    def _pyramid_forward(self, plan, run):
        if plan.pyramid is not None:
            return X.make_pyramid_forward(plan)
        # VMEM-budget fallback: run as fuse="levels"
        return X.compiled_jit(run)

    def _pyramid_inverse(self, plan, run):
        if plan.pyramid is not None:
            return X.make_pyramid_inverse(plan)
        return X.compiled_jit(run)

    def launches(self, plan) -> int:
        """Step kernels per execution; the forward's polyphase split
        kernel (one a level where ``polyphase.split_fits``) is not
        counted."""
        if plan.key.fuse == "none":
            return plan.num_steps
        if plan.key.fuse == "pyramid" and plan.pyramid is not None:
            return 1
        return len(plan.level_specs)


class XlaBackend(Backend):
    """Grouped ``lax.conv_general_dilated`` execution of the compiled tap
    programs (:mod:`repro.compiler.conv`).

    Each compiled program is composed into one 4-in/4-out filter bank and
    applied as a single conv over the stacked polyphase planes — one conv
    per barrier step under ``fuse="none"``, one fused conv per level
    otherwise, batched over images via the conv's N dimension.  Portable
    to GPU/TPU/CPU through XLA's native conv emitters; no Pallas
    dependency.  ``fuse="pyramid"`` is rejected at plan build: there is
    no in-VMEM split/merge megakernel on this path (use ``"levels"``).
    """

    name = "xla"
    description = ("compiled tap programs as grouped XLA convolutions "
                   "(GPU/TPU/CPU portable)")
    fuse_modes = ("none", "scheme", "levels")
    jit_per_level = True

    def program_opt(self, key) -> Optional[str]:
        # conv lowering composes a *program*; "off" (the raw matrix walk)
        # lowers the unoptimized "exact" program, which is term-for-term
        # the raw walk — composition erases the difference anyway.
        return "exact" if key.tap_opt == "off" else key.tap_opt

    def level_forward(self, x, spec, key):
        return X.xla_level_forward(x, spec, key)

    def level_inverse(self, planes, spec, key):
        return X.xla_level_inverse(planes, spec, key)

    def launches(self, plan) -> int:
        """Grouped-conv calls per execution — the barrier count of the
        scheme (ns-* schemes halve it), measurable on this backend."""
        if plan.key.fuse == "none":
            return plan.num_steps
        return len(plan.level_specs)


class AutoBackend(Backend):
    """Profile-guided meta-backend: ``build_plan`` resolves
    ``backend="auto"`` through :func:`repro.profiler.auto.choose`
    (measured store -> fitted cost model -> cold-start heuristic) and
    builds the plan on the chosen concrete backend — the returned plan's
    ``key.backend`` is the concrete one and ``plan.auto`` records the
    choice.  The ``fuse``/``tap_opt`` arguments of an auto call are
    hints only: the cost model overrides them (documented in
    ``dwt2``); ``validate`` therefore accepts every generic key and the
    chosen backend re-validates after substitution."""

    name = "auto"
    description = ("profile-guided: the measured cost model picks "
                   "(backend, fuse, block, tap_opt) per device")

    def validate(self, key) -> None:
        # any generically-valid key is acceptable; the concrete backend
        # chosen by the cost model re-validates the resolved key
        return None

    def make_forward(self, plan):
        raise BackendError(
            "backend 'auto' resolves to a concrete backend at plan "
            "build; plans never execute on it directly")

    make_inverse = make_forward


register_backend(JnpBackend())
register_backend(PallasBackend())
register_backend(XlaBackend())
register_backend(AutoBackend())
