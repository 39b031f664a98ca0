"""Resilient executor dispatch: retry, then degrade down the chain.

The paper's central property — the same DWT computed by interchangeable
schemes/backends with matching results — is exactly what a production
system should exploit when a path *fails*, not just when it is slow.
:func:`dispatch` wraps every plan execution
(:meth:`repro.engine.plan.DwtPlan.execute` routes here):

1. **retry** the plan's own executor (bounded, backed-off) — transient
   launch failures recover in place;
2. **degrade** down a capability-checked chain
   (``fuse: pyramid → levels → none``, then
   ``backend: pallas → xla → jnp``), re-resolving the plan through the
   LRU cache and **verifying** the fallback output against the jnp
   reference (the exactness contract) before accepting it;
3. record every hop in ``repro_fallbacks_total{from, to, site}``.

A plan whose own kernel fails to lower or compile
(:class:`KernelCompileError`) is a defect, not a fault: it propagates
at once, with no retry and no hop.

Config via env (read once; :func:`reload` re-reads):

* ``REPRO_RESILIENCE=on|off`` — ``off`` restores PR 8 behaviour
  (first failure propagates); default on;
* ``REPRO_RESILIENCE_RETRIES`` — in-place retries before degrading
  (default 1);
* ``REPRO_RESILIENCE_VERIFY=on|off`` — verify fallback outputs against
  the jnp reference (default on; the reference itself is never
  re-verified).

Overhead when nothing fails: one ``try`` frame per execution — the
``--faults-overhead`` CI gate holds the whole plane under 1%.

Import discipline: this module lives in :mod:`repro.faults` (stdlib +
telemetry at import time) and pulls the engine in lazily, so
``engine/plan.py`` can import it at module top without a cycle.
"""
from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Tuple

from repro import telemetry as T
from repro.faults import inject
from repro.faults.policy import DeadlineExceeded, retry_call

FALLBACKS = T.counter(
    "repro_fallbacks_total",
    "Degradation-chain hops taken after executor failure",
    labelnames=("from", "to", "site"))

ENABLE_ENV = "REPRO_RESILIENCE"
RETRIES_ENV = "REPRO_RESILIENCE_RETRIES"
VERIFY_ENV = "REPRO_RESILIENCE_VERIFY"

#: degradation orders (left = most capable); "scheme" degrades to "none"
BACKEND_CHAIN = ("pallas", "xla", "jnp")
FUSE_DEMOTIONS = {"pyramid": ("levels", "none"), "scheme": ("none",),
                  "levels": ("none",), "none": ()}


@dataclasses.dataclass
class ResilienceConfig:
    enabled: bool = True
    retries: int = 1
    backoff_s: float = 0.005
    verify: bool = True


def _from_env() -> ResilienceConfig:
    return ResilienceConfig(
        enabled=os.environ.get(ENABLE_ENV, "on").lower() != "off",
        retries=int(os.environ.get(RETRIES_ENV, "1") or 1),
        verify=os.environ.get(VERIFY_ENV, "on").lower() != "off")


CONFIG = _from_env()


def reload() -> ResilienceConfig:
    """Re-read the ``REPRO_RESILIENCE*`` env vars into :data:`CONFIG`."""
    global CONFIG
    CONFIG = _from_env()
    return CONFIG


class KernelCompileError(RuntimeError):
    """A plan's own executor failed to lower or compile (a Mosaic or XLA
    compile error, a kernel over its VMEM budget).  That is a defect of
    the plan, not a runtime fault: :func:`dispatch` neither retries nor
    degrades it, so a plan never runs on another backend in its place.
    The compiler's error is the ``__cause__``."""


class ExactnessError(RuntimeError):
    """A fallback result disagreed with the jnp reference beyond the
    exactness contract's tolerance — the hop is rejected, the chain
    continues."""


class DegradationExhausted(RuntimeError):
    """Every candidate in the degradation chain failed; carries the
    original executor failure as ``__cause__``."""


def degradation_chain(key) -> List:
    """Capability-checked fallback PlanKeys for ``key``, most-capable
    first: same-backend fuse demotions, then lower backends (each at
    the highest fuse it supports).

    >>> from repro.engine.plan import PlanKey
    >>> k = PlanKey("cdf97", "ns-polyconv", 2, (64, 64), "float32",
    ...             "pallas", False, "pyramid", "periodic")
    >>> [(c.backend, c.fuse) for c in degradation_chain(k)]
    [('pallas', 'levels'), ('pallas', 'none'), ('xla', 'levels'), ('jnp', 'levels')]
    """
    from repro.engine import backends as B
    out, seen = [], {(key.backend, key.fuse)}

    def admit(cand) -> None:
        tag = (cand.backend, cand.fuse)
        if tag in seen:
            return
        try:
            B.get_backend(cand.backend).validate(cand)
        except Exception:
            return
        seen.add(tag)
        out.append(cand)

    demotions = FUSE_DEMOTIONS.get(key.fuse, ("none",))
    for f in demotions:
        admit(dataclasses.replace(key, fuse=f))
    start = (BACKEND_CHAIN.index(key.backend) + 1
             if key.backend in BACKEND_CHAIN else 0)
    # backend hops also demote fuse: the failing mode is not retried on
    # the weaker backend, only its demotions (or "none" when already
    # there) — the chain's tail is always the jnp reference path
    for b in BACKEND_CHAIN[start:]:
        n = len(out)
        for f in demotions or ("none",):
            admit(dataclasses.replace(key, backend=b, fuse=f))
            if len(out) > n:    # highest supported fuse on b is enough
                break
    return out


def _tolerance(key) -> Tuple[float, float]:
    """The exactness contract across chain hops: same transform, other
    path.  Float32 paths agree to fp-accumulation order; bf16 compute
    is inherently coarser."""
    if key.compute_dtype == "bfloat16":
        return 2e-2, 2e-2
    return 1e-3, 1e-4


def _leaves(result) -> List:
    if isinstance(result, (tuple, list)):
        out = []
        for r in result:
            out.extend(_leaves(r))
        return out
    return [result]


def _has_nonfinite(result) -> bool:
    import numpy as np
    return any(not np.isfinite(np.asarray(leaf)).all()
               for leaf in _leaves(result))


def _verify(result, reference, key) -> None:
    import numpy as np
    got, want = _leaves(result), _leaves(reference)
    rtol, atol = _tolerance(key)
    if len(got) != len(want):
        raise ExactnessError(
            f"fallback produced {len(got)} planes, reference {len(want)}")
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape or not np.allclose(
                g.astype(np.float64), w.astype(np.float64),
                rtol=rtol, atol=atol, equal_nan=False):
            raise ExactnessError(
                f"fallback output disagrees with the jnp reference "
                f"beyond the exactness contract (rtol={rtol}, "
                f"atol={atol}) for {key.scheme} on {key.backend}")


def _reference_key(key):
    return dataclasses.replace(key, backend="jnp", fuse="none")


def _run_key(cand, op: str, args):
    """Build (via the LRU cache) and run one candidate plan, raw —
    bypassing plan.execute so a fallback never recursively dispatches
    into its own recovery."""
    from repro.engine import cache as EC
    plan = EC.global_cache().get(cand)
    fn = plan._forward if op == "forward" else plan._inverse
    return fn(*args)


def dispatch(plan, op: str, args) -> object:
    """Run ``plan``'s ``op`` executor with retry + degradation.

    ``op`` is ``"forward"`` (args = ``(x,)``) or ``"inverse"``
    (args = ``(ll, details)``).  Raises the *original* executor failure
    (as ``DegradationExhausted.__cause__``) when every chain hop fails.
    """
    site = f"execute.{op}"
    fn = plan._forward if op == "forward" else plan._inverse

    def attempt():
        inject.maybe_inject(site, backend=plan.key.backend,
                            fuse=plan.key.fuse)
        out = fn(*args)
        if inject.active() is not None:
            out = inject.corrupt_output(site, out)
            # silent-corruption detection is only armed while the fault
            # plane is active: the finite-ness sweep forces a device
            # sync, which production must not pay
            if _has_nonfinite(out):
                raise ExactnessError(
                    f"non-finite values in {site} output "
                    f"(backend={plan.key.backend}, fuse={plan.key.fuse})")
        return out

    cfg = CONFIG
    if not cfg.enabled:
        return attempt()
    try:
        return retry_call(attempt, site=site, retries=cfg.retries,
                          backoff_s=cfg.backoff_s,
                          fatal=(KernelCompileError,))
    except (DeadlineExceeded, KernelCompileError):
        raise
    except Exception as err:
        return _degrade(plan, op, args, err)


def _degrade(plan, op: str, args, err: Exception):
    key = plan.key
    site = getattr(err, "site", f"execute.{op}")
    src = f"{key.backend}/{key.fuse}"
    last = err
    for cand in degradation_chain(key):
        try:
            out = _run_key(cand, op, args)
            if CONFIG.verify and not (cand.backend == "jnp"
                                      and cand.fuse == "none"):
                ref = _run_key(_reference_key(key), op, args)
                _verify(out, ref, key)
            FALLBACKS.inc(**{"from": src, "to":
                             f"{cand.backend}/{cand.fuse}", "site": site})
            return out
        except Exception as e:          # try the next, weaker hop
            last = e
    raise DegradationExhausted(
        f"all degradation candidates failed for {src} after {site} "
        f"failure (last: {type(last).__name__}: {last})") from err


def stats() -> dict:
    """The resilience slice of ``engine.stats()['faults']``."""
    fb = sum(row["value"] for row in FALLBACKS.series())
    from repro.faults.policy import RETRIES
    rt = sum(row["value"] for row in RETRIES.series())
    return {"enabled": CONFIG.enabled, "fallbacks": int(fb),
            "retries": int(rt)}
