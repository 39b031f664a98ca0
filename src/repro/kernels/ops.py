"""Public jit'd wrappers for the Pallas DWT kernels.

``apply_scheme_pallas`` is the single-level dispatch point used by the
benchmarks and the kernel tests; multi-level execution goes through the
plan/executor engine (``repro.engine``), which shares the same memoized
scheme-step construction (``repro.engine.plan.scheme_steps``) so a scheme
is factored into StepSpecs exactly once per configuration process-wide.
Only the plane arithmetic is traced; inputs may be batched ``(..., H, W)``
— the batch rides the kernel's leading grid dimension.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import optimize as O
from repro.core import schemes as S
from repro.kernels import polyphase as PP


def _scheme_steps(wavelet: str, scheme: str, optimize: bool, inverse: bool):
    # deferred import: repro.engine.plan imports this module's package
    from repro.engine.plan import scheme_steps
    return scheme_steps(wavelet, scheme, optimize, inverse)


@functools.partial(
    jax.jit,
    static_argnames=("wavelet", "scheme", "optimize", "inverse", "fuse",
                     "block", "interpret", "compute_dtype", "tap_opt"))
def apply_scheme_pallas(x, *, wavelet: str = "cdf97",
                        scheme: str = "ns-polyconv",
                        optimize: bool = False,
                        inverse: bool = False,
                        fuse: str = "none",
                        block: Tuple[int, int] = (256, 512),
                        interpret: Optional[bool] = None,
                        compute_dtype: str = "float32",
                        tap_opt: str = "full"):
    """Single-level 2-D DWT step sequence on TPU via Pallas.

    Forward: ``x`` is a (batch of) image(s) (..., H, W) -> returns the
    (LL, HL, LH, HH) planes, each (..., H/2, W/2).
    Inverse: ``x`` is the 4-tuple of planes -> returns the image(s).

    ``tap_opt`` picks the tap-program compilation level ("off" = raw
    matrix walk); ``compute_dtype`` the in-kernel arithmetic dtype.
    """
    from repro import compiler as C
    cdt = jnp.dtype(compute_dtype)
    kfuse = "none" if fuse == "none" else "scheme"
    programs = (None if tap_opt == "off" else
                C.compile_scheme_programs(wavelet, scheme,
                                          bool(optimize) and not inverse,
                                          inverse, tap_opt, kfuse))
    if inverse:
        steps = _scheme_steps(wavelet, scheme, False, True)
        out = PP.apply_steps_pallas(steps, tuple(x), fuse=kfuse,
                                    block=block, interpret=interpret,
                                    compute_dtype=cdt, tap_opt=tap_opt,
                                    programs=programs, inverse=True)
        return S.from_planes(out)
    steps = _scheme_steps(wavelet, scheme, optimize, False)
    planes = S.to_planes(x)
    return PP.apply_steps_pallas(steps, planes, fuse=kfuse, block=block,
                                 interpret=interpret, compute_dtype=cdt,
                                 tap_opt=tap_opt, programs=programs)


def scheme_stats(wavelet: str, scheme: str, optimize: bool,
                 shape: Tuple[int, int], itemsize: int = 4,
                 fuse: str = "none", tap_opt: str = "full") -> dict:
    """Step count / op counts / ideal HBM bytes for the roofline model.

    ``fuse`` accepts the engine's level-granularity modes too: "scheme",
    "levels" and "pyramid" all collapse one level to one pallas_call
    (for the multi-level pyramid model see
    :func:`repro.kernels.polyphase.pyramid_hbm_bytes`).  ``ops`` is
    the paper-convention raw matrix count; ``ops_compiled`` (and
    ``macs_per_pixel``) come straight from the compiled tap program that
    the kernels actually execute, so measured MACs/pixel are comparable
    against the paper's operation-count tables.
    """
    from repro import compiler as C
    sch = (O.build_optimized(wavelet, scheme) if optimize
           else S.build_scheme(wavelet, scheme))
    steps = PP.steps_of(sch)
    kfuse = "none" if fuse == "none" else "scheme"
    calls = 1 if kfuse == "scheme" else len(steps)
    programs = (None if tap_opt == "off" else
                C.compile_scheme_programs(wavelet, scheme, optimize, False,
                                          tap_opt, kfuse))
    out = {
        "wavelet": wavelet,
        "scheme": scheme + ("+opt" if optimize else ""),
        "fuse": fuse,
        "steps": len(steps),
        "pallas_calls": calls,
        "ops": sch.num_ops,
        "hbm_bytes": PP.scheme_hbm_bytes(steps, shape, itemsize, fuse=kfuse,
                                         programs=programs),
    }
    if programs is not None:
        cst = C.program_stats(programs)
        out["ops_compiled"] = cst["macs"]
        out["macs_per_pixel"] = cst["macs_per_pixel"]
        out["halo_compiled"] = cst["halo"]
    return out
