"""Multi-level 2-D DWT / inverse DWT public API (engine-backed).

This is the user-facing entry point of the core library:

    pyr  = dwt2(img, wavelet="cdf97", levels=3, scheme="ns-polyconv")
    img2 = idwt2(pyr, wavelet="cdf97", scheme="ns-polyconv")

A pyramid is ``(LL_L, [(HL_l, LH_l, HH_l) for l in L..1])`` — the coarsest
approximation plus per-level detail triples, finest last.

Both functions are thin wrappers over the plan/executor engine
(:mod:`repro.engine`): every call resolves a :class:`repro.engine.DwtPlan`
from the LRU plan cache keyed on
``(wavelet, scheme, levels, shape, dtype, backend, optimize, fuse,
boundary, compute_dtype, tap_opt, tiles)`` — the scheme algebra,
per-level step sequences, block shapes
and halo pads are computed once per key and reused across calls.  Input
may be batched ``(..., H, W)`` on both backends; batches run in a single
kernel launch per barrier (a leading grid dimension on the Pallas path).

Parameters shared by :func:`dwt2` and :func:`idwt2`:

``backend``
    Any backend registered in :mod:`repro.engine.backends`
    (``repro.engine.available_backends()`` lists them).  Built-ins:

    * "jnp"     — pure-jnp reference (roll-based periodic convolution)
    * "pallas"  — the Pallas kernels (Mosaic on a TPU, the Pallas
      interpreter on the CPU; refused on other platforms)
    * "xla"     — compiled tap programs as grouped
      ``lax.conv_general_dilated`` calls (one fused conv per step;
      GPU/TPU/CPU-portable, no Pallas dependency)
    * "auto"    — profile-guided: the measured cost model in
      :mod:`repro.profiler` picks the concrete
      ``(backend, fuse, block, tap_opt)`` for this device at plan
      build (trace store at ``$REPRO_PROFILE_STORE``, cold-start
      heuristic when empty).  ``fuse``/``tap_opt`` arguments become
      hints the selector may override; output is bit-identical to
      calling the chosen configuration manually.

    Unknown backends and unsupported (backend, configuration)
    combinations raise at plan build with the offending field named.
``optimize``
    ``True`` applies the paper's Section 5 operation-reduction split
    (identical values, fewer MACs).
``fuse``
    * "none"    — paper-faithful: one barrier (pallas_call) per step
    * "scheme"  — one pallas_call per level (compound halo); affects
      only the pallas backend (jnp has no kernel granularity to fuse)
    * "levels"  — the whole multi-level pyramid is one traced
      computation; level kernels chain without returning to Python
      between levels (fastest dispatch for repeated traffic)
    * "pyramid" — the whole multi-level pyramid is a **single
      pallas_call**: polyphase split/merge happens in-VMEM on
      compound-halo windows of the interleaved image and the LL plane
      never round-trips through HBM between levels (fewest bytes
      moved).  CPU (interpreter) only: a TPU plan build refuses it,
      since Mosaic does not lower its in-VMEM polyphase split.  Falls
      back to "levels" execution when the compound window exceeds
      the VMEM budget (``$REPRO_PYRAMID_VMEM_LIMIT``);
      on the jnp backend it runs the eager per-level chain,
      bit-identical to "none".
``boundary``
    Signal-extension rule at image edges.  Only ``"periodic"`` is
    implemented (matching the paper's polyphase algebra, where every
    z-transform shift is a cyclic shift); the parameter is part of the
    plan key so additional modes can be added without API changes.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from repro.engine.pyramid import (Detail, Pyramid,  # re-exported for compat
                                  Pyramid3, WaveletPacket2D)

__all__ = ["Pyramid", "Pyramid3", "WaveletPacket2D", "dwt2", "idwt2",
           "dwt3", "idwt3", "wpt2", "iwpt2", "best_basis",
           "flatten_pyramid", "unflatten_pyramid", "validate_finite",
           "VALIDATE_MODES"]

#: accepted values of the ``validate`` parameter (None = no checking)
VALIDATE_MODES = (None, "nan")


def validate_finite(x, mode, what: str = "input") -> None:
    """Opt-in input validation at the plan boundary.

    ``mode=None`` is a no-op (the production default: validation costs a
    full device sync + sweep).  ``mode="nan"`` rejects arrays containing
    NaN/Inf with an actionable error *before* the transform runs —
    garbage coefficients otherwise propagate silently through every
    pyramid level and into downstream consumers.  Pyramids are checked
    plane by plane.
    """
    if mode is None:
        return
    if mode not in VALIDATE_MODES:
        raise ValueError(f"unknown validate mode {mode!r}; "
                         f"available: {VALIDATE_MODES}")
    import numpy as np
    if isinstance(x, Pyramid):
        validate_finite(x.ll, mode, what=f"{what} (LL plane)")
        for lvl, dd in enumerate(x.details):
            for band, d in zip(("HL", "LH", "HH"), dd):
                validate_finite(d, mode,
                                what=f"{what} ({band} plane, level {lvl})")
        return
    if isinstance(x, Pyramid3):
        validate_finite(x.ll, mode, what=f"{what} (tLLL volume)")
        for lvl, dd in enumerate(x.details):
            for band, d in enumerate(dd):
                validate_finite(d, mode,
                                what=f"{what} (subband {band}, "
                                     f"level {lvl})")
        return
    if isinstance(x, WaveletPacket2D):
        for path, leaf in x.items():
            validate_finite(leaf, mode, what=f"{what} (leaf {path!r})")
        return
    arr = np.asarray(x)
    if not np.isfinite(arr).all():
        bad = int(arr.size - np.isfinite(arr).sum())
        raise ValueError(
            f"{what} contains {bad} non-finite value(s) (NaN/Inf), "
            f"rejected by validate='nan' at the plan boundary; sanitize "
            f"the input or drop validate to accept it")


def _plan_for(shape, dtype, wavelet, levels, scheme, optimize, backend,
              fuse, boundary, compute_dtype, tap_opt, tiles=None,
              packet=None, ndim=2, bit_depth=None):
    from repro import engine as E  # deferred: core <-> engine import cycle
    return E.get_plan(wavelet=wavelet, scheme=scheme, levels=levels,
                      shape=tuple(shape), dtype=str(dtype), backend=backend,
                      optimize=optimize, fuse=fuse, boundary=boundary,
                      compute_dtype=compute_dtype, tap_opt=tap_opt,
                      tiles=tiles, packet=packet, ndim=ndim,
                      bit_depth=bit_depth)


def dwt2(x: jax.Array, wavelet: str = "cdf97", levels: int = 1,
         scheme: str = "ns-polyconv", optimize: bool = False,
         backend: str = "jnp", fuse: str = "none",
         boundary: str = "periodic", compute_dtype: str = "float32",
         tap_opt: str = "full", tiles=None, validate=None,
         bit_depth=None) -> Pyramid:
    """Multi-level forward 2-D DWT of a (batch of) image(s) (..., H, W).

    H and W must be divisible by 2**levels.  Dispatches through the
    plan-cache engine; see the module docstring for ``backend`` /
    ``optimize`` / ``fuse`` / ``boundary``.  ``compute_dtype``
    ("float32" or "bfloat16") sets the arithmetic dtype inside the
    kernels — I/O stays in the input dtype.  ``tap_opt`` selects the
    tap-program compiler level ("off" walks the raw polyphase matrices,
    "exact" compiles without reassociation, "full" — the default —
    applies fold/CSE/rank-1 and cuts the in-kernel MACs).  "exact" is
    bit-identical to "off" on the ``pallas`` backend (both accumulate
    term by term, cf. ``_apply_matrix_windows``); the jnp "off" walk
    uses the legacy per-entry accumulation tree, so "exact" matches it
    only to ulp-level rounding there.  ``tiles`` (a ``(tile_h, tile_w)``
    pair, or None) runs the transform over a grid of halo-padded tiles
    instead of one monolithic plane — same coefficients (bit-identical
    at ``tap_opt`` "off"/"exact"), tiled execution; see
    :mod:`repro.tiling`.  ``validate="nan"`` (opt-in; default off)
    rejects NaN/Inf inputs at the plan boundary with an actionable
    error instead of propagating garbage coefficients
    (:func:`validate_finite`).

    Integer samples (an unsigned ``x``, e.g. ``uint16``) need
    ``bit_depth``, their precision in bits: the transform then sees
    ``x - 2**(bit_depth - 1)`` (the DC level shift of ISO/IEC 15444-1
    Annex G.1.2), computed in ``compute_dtype``, and the coefficients
    are float32.  Integer input without ``bit_depth``, or with one
    larger than its dtype holds, raises a ``ValueError``; so does a
    ``bit_depth`` with float input, which is taken as already shifted.

    >>> import jax.numpy as jnp
    >>> from repro.core import dwt2
    >>> img = jnp.ones((2, 16, 16))          # batch of 2, periodic 16x16
    >>> pyr = dwt2(img, wavelet="cdf53", levels=2, scheme="sep-lifting")
    >>> pyr.levels, pyr.ll.shape
    (2, (2, 4, 4))
    >>> [tuple(d.shape for d in det) for det in pyr.details]  # coarse first
    [((2, 4, 4), (2, 4, 4), (2, 4, 4)), ((2, 8, 8), (2, 8, 8), (2, 8, 8))]
    >>> pyr2 = dwt2(img, wavelet="cdf53", levels=2, scheme="ns-conv",
    ...             backend="xla")           # same coefficients, 1 conv/step
    >>> bool(jnp.allclose(pyr.ll, pyr2.ll, atol=1e-5))
    True
    """
    x = jnp.asarray(x)
    validate_finite(x, validate, what="dwt2 input")
    plan = _plan_for(x.shape, x.dtype, wavelet, levels, scheme, optimize,
                     backend, fuse, boundary, compute_dtype, tap_opt, tiles,
                     bit_depth=bit_depth)
    return plan.execute(x)


def idwt2(pyr: Pyramid, wavelet: str = "cdf97",
          scheme: str = "ns-polyconv", optimize: bool = False,
          backend: str = "jnp", fuse: str = "none",
          boundary: str = "periodic", compute_dtype: str = "float32",
          tap_opt: str = "full", tiles=None, validate=None) -> jax.Array:
    """Inverse of :func:`dwt2` (shares the forward transform's plan
    cache key family; pass the same ``wavelet``/``scheme``/backend
    arguments as the forward call).  ``validate="nan"`` rejects
    pyramids with NaN/Inf coefficient planes at the plan boundary.

    >>> import jax.numpy as jnp
    >>> from repro.core import dwt2, idwt2
    >>> x = jnp.arange(256.0).reshape(16, 16)
    >>> pyr = dwt2(x, wavelet="cdf97", levels=2, scheme="ns-polyconv")
    >>> rec = idwt2(pyr, wavelet="cdf97", scheme="ns-polyconv")
    >>> rec.shape == x.shape                 # perfect reconstruction
    True
    >>> bool(jnp.allclose(rec, x, atol=1e-3))
    True
    """
    validate_finite(pyr, validate, what="idwt2 input pyramid")
    ll = jnp.asarray(pyr.ll)
    levels = pyr.levels
    shape = ll.shape[:-2] + (ll.shape[-2] << levels, ll.shape[-1] << levels)
    plan = _plan_for(shape, ll.dtype, wavelet, levels, scheme, optimize,
                     backend, fuse, boundary, compute_dtype, tap_opt, tiles)
    return plan.execute_inverse(pyr)


def wpt2(x: jax.Array, wavelet: str = "cdf97", packet="full:2",
         scheme: str = "ns-polyconv", optimize: bool = False,
         backend: str = "jnp", fuse: str = "none",
         boundary: str = "periodic", compute_dtype: str = "float32",
         tap_opt: str = "full", validate=None) -> WaveletPacket2D:
    """2-D wavelet **packet** transform of a (batch of) image(s).

    Where :func:`dwt2` recurses into the LL subband only, a packet
    transform may split any node of the subband quad-tree.  ``packet``
    names the decomposition: ``"full:D"`` (the complete depth-D tree),
    ``"dwt:L"`` (the plain pyramid, as a packet tree), an iterable of
    leaf paths over the child alphabet ``a/h/v/d`` (a=LL, h=HL, v=LH,
    d=HH), or a :class:`repro.core.packets.PacketTree` — e.g. one
    pruned by :func:`best_basis`.  H and W must be divisible by
    ``2**depth``.  Every admissible leaf set reconstructs exactly via
    :func:`iwpt2`; plans are cached on the canonical leaf tuple, so
    equivalent spellings of one tree share a plan.

    >>> import jax.numpy as jnp
    >>> from repro.core import wpt2, iwpt2
    >>> img = jnp.arange(256.0).reshape(16, 16)
    >>> pk = wpt2(img, wavelet="cdf53", packet="full:2")
    >>> len(pk.paths), pk.leaves[0].shape     # 16 leaves, 4x4 each
    (16, (4, 4))
    >>> pk.paths[:4]
    ('aa', 'ah', 'av', 'ad')
    >>> pk2 = wpt2(img, wavelet="cdf53",      # mixed-depth leaf set
    ...            packet=("aa", "ah", "av", "ad", "h", "v", "d"))
    >>> rec = iwpt2(pk2, wavelet="cdf53")
    >>> bool(jnp.allclose(rec, img, atol=1e-3))
    True
    """
    x = jnp.asarray(x)
    validate_finite(x, validate, what="wpt2 input")
    plan = _plan_for(x.shape, x.dtype, wavelet, 1, scheme, optimize,
                     backend, fuse, boundary, compute_dtype, tap_opt,
                     packet=packet)
    return plan.execute(x)


def iwpt2(pk: WaveletPacket2D, wavelet: str = "cdf97",
          scheme: str = "ns-polyconv", optimize: bool = False,
          backend: str = "jnp", fuse: str = "none",
          boundary: str = "periodic", compute_dtype: str = "float32",
          tap_opt: str = "full", validate=None) -> jax.Array:
    """Inverse of :func:`wpt2`: exact reconstruction from any
    admissible leaf set (the packet tree is read off ``pk.paths``)."""
    validate_finite(pk, validate, what="iwpt2 input packet")
    first = jnp.asarray(pk.leaves[0])
    d = len(pk.paths[0])
    shape = first.shape[:-2] + (first.shape[-2] << d,
                                first.shape[-1] << d)
    plan = _plan_for(shape, first.dtype, wavelet, 1, scheme, optimize,
                     backend, fuse, boundary, compute_dtype, tap_opt,
                     packet=tuple(pk.paths))
    return plan.execute_inverse(pk)


def best_basis(x: jax.Array, wavelet: str = "cdf97", depth: int = 2,
               cost: str = "shannon", scheme: str = "ns-polyconv",
               optimize: bool = False, backend: str = "jnp",
               fuse: str = "none", boundary: str = "periodic",
               compute_dtype: str = "float32", tap_opt: str = "full"):
    """Entropy-pruned packet tree for ``x`` (Coifman–Wickerhauser).

    Decomposes the full quad-tree to ``depth``, scores every node with
    the additive ``cost`` functional (``"shannon"``, ``"l1"`` or
    ``"threshold"``; see :mod:`repro.core.packets`) and keeps a node
    whole when splitting does not pay.  The returned
    :class:`~repro.core.packets.PacketTree` feeds straight into
    :func:`wpt2`'s ``packet`` argument.

    >>> import jax.numpy as jnp
    >>> from repro.core import best_basis, wpt2
    >>> smooth = jnp.ones((16, 16))           # nothing to split for
    >>> tree = best_basis(smooth, wavelet="cdf53", depth=2)
    >>> tree.leaves                           # root split only
    ('a', 'h', 'v', 'd')
    >>> pk = wpt2(smooth, wavelet="cdf53", packet=tree)
    >>> len(pk.leaves)
    4
    """
    from repro.core import packets as PK
    import numpy as np
    if cost not in PK.COSTS:
        raise ValueError(f"unknown cost {cost!r}; "
                         f"available: {sorted(PK.COSTS)}")
    cost_fn = PK.COSTS[cost]
    x = jnp.asarray(x)
    costs = {}

    def walk(img, path):
        costs[path] = cost_fn(np.asarray(img))
        if len(path) == depth:
            return
        pyr = dwt2(img, wavelet=wavelet, levels=1, scheme=scheme,
                   optimize=optimize, backend=backend, fuse=fuse,
                   boundary=boundary, compute_dtype=compute_dtype,
                   tap_opt=tap_opt)
        hl, lh, hh = pyr.details[0]
        for c, arr in zip(PK.CHILDREN, (pyr.ll, hl, lh, hh)):
            walk(arr, path + c)

    walk(x, "")
    return PK.best_basis_from_costs(costs, depth)


def dwt3(x: jax.Array, wavelet: str = "cdf97", levels: int = 1,
         scheme: str = "ns-polyconv", optimize: bool = False,
         backend: str = "jnp", fuse: str = "none",
         boundary: str = "periodic", compute_dtype: str = "float32",
         tap_opt: str = "full", validate=None) -> Pyramid3:
    """Multi-level 3-D (t+2D) DWT of a (batch of) volume(s)
    ``(..., T, H, W)``.

    Each level lifts along the temporal axis (1-D periodic lifting of
    the wavelet's predict/update pairs, compiled once per wavelet —
    :mod:`repro.compiler.temporal`) and transforms both temporal
    half-bands with the compiled 2-D level of the chosen backend (the
    T/2 frames ride the free leading batch dims); only the tL·LL
    subband recurses.  T, H and W must each be divisible by
    ``2**levels``.  On the jnp and xla backends ``fuse="levels"`` fuses
    the t+2D chain into one trace; pallas keeps the temporal pass
    unfused (capability-checked fallback, recorded on
    ``plan.fallback``).  ``fuse="pyramid"`` demotes to ``"levels"`` —
    the megakernel is 2-D-pyramid-only.

    >>> import jax.numpy as jnp
    >>> from repro.core import dwt3, idwt3
    >>> vid = jnp.ones((8, 16, 16))           # T=8 frames of 16x16
    >>> p3 = dwt3(vid, wavelet="cdf53", levels=2)
    >>> p3.levels, p3.ll.shape                # coarsest tLLL volume
    (2, (2, 4, 4))
    >>> [d[0].shape for d in p3.details]      # 7 subbands/level
    [(2, 4, 4), (4, 8, 8)]
    >>> rec = idwt3(p3, wavelet="cdf53")
    >>> bool(jnp.allclose(rec, vid, atol=1e-4))
    True
    """
    x = jnp.asarray(x)
    validate_finite(x, validate, what="dwt3 input")
    plan = _plan_for(x.shape, x.dtype, wavelet, levels, scheme, optimize,
                     backend, fuse, boundary, compute_dtype, tap_opt,
                     ndim=3)
    return plan.execute(x)


def idwt3(pyr: Pyramid3, wavelet: str = "cdf97",
          scheme: str = "ns-polyconv", optimize: bool = False,
          backend: str = "jnp", fuse: str = "none",
          boundary: str = "periodic", compute_dtype: str = "float32",
          tap_opt: str = "full", validate=None) -> jax.Array:
    """Inverse of :func:`dwt3` (pass the same ``wavelet`` / ``scheme``
    / backend arguments as the forward call)."""
    validate_finite(pyr, validate, what="idwt3 input pyramid")
    ll = jnp.asarray(pyr.ll)
    levels = pyr.levels
    shape = ll.shape[:-3] + (ll.shape[-3] << levels,
                             ll.shape[-2] << levels,
                             ll.shape[-1] << levels)
    plan = _plan_for(shape, ll.dtype, wavelet, levels, scheme, optimize,
                     backend, fuse, boundary, compute_dtype, tap_opt,
                     ndim=3)
    return plan.execute_inverse(pyr)


def flatten_pyramid(pyr: Pyramid) -> jax.Array:
    """Pack a pyramid back into a single (..., H, W) array (in-place
    subband layout, JPEG 2000 style: LL in the top-left corner)."""
    ll = pyr.ll
    for hl, lh, hh in pyr.details:
        top = jnp.concatenate([ll, hl], axis=-1)
        bot = jnp.concatenate([lh, hh], axis=-1)
        ll = jnp.concatenate([top, bot], axis=-2)
    return ll


def unflatten_pyramid(x: jax.Array, levels: int) -> Pyramid:
    """Inverse of :func:`flatten_pyramid`."""
    details: List[Detail] = []
    cur = x
    for _ in range(levels):
        h, w = cur.shape[-2] // 2, cur.shape[-1] // 2
        ll = cur[..., :h, :w]
        hl = cur[..., :h, w:]
        lh = cur[..., h:, :w]
        hh = cur[..., h:, w:]
        details.append((hl, lh, hh))
        cur = ll
    return Pyramid(cur, details[::-1])
