"""Conv lowering: tap programs -> fused filter banks for XLA convolution.

Every node of a :class:`~repro.compiler.ir.TapProgram` is a *linear*
function of the four input polyphase planes, so the whole program — no
matter how many lifting/matrix stages it chains — is one linear map from
4 input planes to 4 output planes with finite support.  This pass
composes the SSA chain symbolically into that closed form:

    out_o[n, m] = sum_j sum_{(km, kn)}  W[o, j, kn, km] * in_j[n-kn, m-km]

i.e. a single 4-in / 4-out bank of 2-D FIR filters (:class:`ConvSpec`),
which :func:`run_planes_conv` applies as ONE
``lax.conv_general_dilated`` call per program — batched over images via
the conv's N dimension, with the planes riding the feature channels.

This is the ``backend="xla"`` execution path: the barrier structure of a
scheme survives exactly (one grouped conv per compiled program = one
conv per *step* under ``fuse="none"``, one fused conv per *level*
otherwise — the paper's step counting on a third backend), while the
lowering itself is portable XLA: it runs on GPU, TPU and CPU with no
Pallas dependency, and XLA's conv emitters (cuDNN on NVIDIA, MIOpen on
AMD, the MXU convolution path on TPU) do the vectorization.

Composition note: folding the chain into a dense filter re-associates
the floating-point arithmetic, so the lowered conv matches the program
walk to fp tolerance, not bitwise (compose-time arithmetic is done in
float64 to keep the composed taps accurate to ~1 ulp of float32).  The
dense tap count can exceed the factored program's MAC count — the
classic separable-vs-dense trade the source papers measure
(arXiv:1705.08266): the conv path buys fewer launches and XLA-native
portability at the cost of re-densified arithmetic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.compiler import ir

__all__ = ["ConvSpec", "lower_program_to_conv", "conv_stats",
           "run_planes_conv"]


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """A composed filter bank: one grouped convolution.

    ``weights`` is ``(4, 4, KH, KW)`` float64 in OIHW layout (output
    plane, input plane, row tap, column tap); ``pad = (rn, rm)`` is the
    periodic pad radius per axis, with the zero shift sitting at kernel
    index ``(rn, rm)`` so ``KH = 2*rn + 1`` and ``KW = 2*rm + 1``.
    """

    weights: np.ndarray
    pad: Tuple[int, int]

    @property
    def taps(self) -> int:
        """Nonzero taps = MACs per output quad of the grouped conv."""
        return int(np.count_nonzero(self.weights))

    @property
    def kernel_shape(self) -> Tuple[int, int]:
        return self.weights.shape[2], self.weights.shape[3]


@functools.lru_cache(maxsize=512)
def lower_program_to_conv(prog: ir.TapProgram) -> ConvSpec:
    """Compose a tap program into a single 4x4 bank of 2-D filters.

    Walks the SSA nodes in order, carrying for each node its closed-form
    taps ``{(j, km, kn): c}`` over the *input* planes; a lincomb node's
    taps are the shift-composed, coefficient-scaled union of its terms'
    source taps.  Exact zeros produced by cancellation are dropped.
    """
    taps: List[Dict[Tuple[int, int, int], float]] = []
    for nd in prog.nodes:
        if nd.kind == "input":
            taps.append({(nd.j, 0, 0): 1.0})
            continue
        acc: Dict[Tuple[int, int, int], float] = {}
        for t in nd.terms:
            for (j, km, kn), c in taps[t.src].items():
                k = (j, t.km + km, t.kn + kn)
                acc[k] = acc.get(k, 0.0) + t.c * c
        taps.append({k: c for k, c in acc.items() if c != 0.0})
    outs = [taps[o] for o in prog.outputs]
    rm = max((abs(km) for tp in outs for (_, km, _) in tp), default=0)
    rn = max((abs(kn) for tp in outs for (_, _, kn) in tp), default=0)
    w = np.zeros((4, 4, 2 * rn + 1, 2 * rm + 1), np.float64)
    for o, tp in enumerate(outs):
        for (j, km, kn), c in tp.items():
            w[o, j, rn - kn, rm - km] = c
    w.setflags(write=False)
    return ConvSpec(weights=w, pad=(rn, rm))


def conv_stats(specs: Sequence[ConvSpec]) -> dict:
    """Aggregate cost of a lowered conv sequence (one transform level):
    grouped-conv launches, total nonzero taps (MACs/quad), the largest
    kernel support and the largest pad radius."""
    kh = max((s.kernel_shape[0] for s in specs), default=1)
    kw = max((s.kernel_shape[1] for s in specs), default=1)
    return {"convs": len(specs),
            "taps": sum(s.taps for s in specs),
            "kernel": (kh, kw),
            "halo": max((max(s.pad) for s in specs), default=0)}


def _wrap_pad(x: jax.Array, rn: int, rm: int) -> jax.Array:
    """Periodic pad of the two trailing axes by ``(rn, rm)``; radii larger
    than the plane are fine (tiny odd shapes).  Slices and concatenation,
    not a gather: XLA:CPU in jax 0.9 miscompiles a mod-indexed
    ``jnp.take`` feeding the conv (garbage output at e.g. 16x16 planes)."""
    cfg = [(0, 0)] * (x.ndim - 2) + [(rn, rn), (rm, rm)]
    return jnp.pad(x, cfg, mode="wrap")


def _apply_conv(x: jax.Array, spec: ConvSpec) -> jax.Array:
    """One grouped conv: (N, 4, h, w) -> (N, 4, h, w), periodic boundary.

    ``Precision.HIGHEST``: at the default precision the TPU's MXU rounds
    float32 operands to bfloat16 (one pass), ~1e-3 relative error — three
    orders of magnitude outside the cross-backend parity tolerance.  On
    the CPU the flag changes nothing."""
    rn, rm = spec.pad
    xp = _wrap_pad(x, rn, rm)
    w = jnp.asarray(spec.weights, x.dtype)
    return jax.lax.conv_general_dilated(
        xp, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)


def run_planes_conv(programs: Sequence[ir.TapProgram], planes: Sequence,
                    compute_dtype=jnp.float32):
    """Execute a compiled program sequence over four batched ``(..., h, w)``
    polyphase planes as grouped convolutions (one conv per program).

    The four planes stack onto a feature-channel axis and the leading
    batch dims flatten onto the conv's N dimension, so a whole batch is
    one XLA conv per barrier.  Arithmetic runs in ``compute_dtype``; I/O
    stays in the planes' dtype (matching the jnp/pallas executors).
    """
    out_dtype = planes[0].dtype
    x = jnp.stack([jnp.asarray(p) for p in planes], axis=-3)
    lead = x.shape[:-3]
    x = x.reshape((-1, 4) + x.shape[-2:]).astype(compute_dtype)
    for prog in programs:
        x = _apply_conv(x, lower_program_to_conv(prog))
    x = x.reshape(lead + (4,) + x.shape[-2:]).astype(out_dtype)
    return tuple(x[..., j, :, :] for j in range(4))
