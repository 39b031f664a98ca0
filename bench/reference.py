"""Plain float64 NumPy reference of the periodic multi-level 2-D DWT.

Written from the published lifting factorisations, not from the engine:
it imports nothing of ``repro`` and takes nothing the engine made.  Each
level lifts every row (along W), then every column (along H):

    s = x[0::2], d = x[1::2]                      (even / odd samples)
    for (p, u) in pairs:
        d[n] += p * (s[n] + s[n+1])               (predict)
        s[n] += u * (d[n-1] + d[n])               (update)
    s *= zeta;  d /= zeta                         (scaling)

with periodic extension at both ends.  The subbands of one level are
``LL = (s_H, s_W)``, ``HL = (s_H, d_W)``, ``LH = (d_H, s_W)`` and
``HH = (d_H, d_W)``; a pyramid is ``(LL_L, [(HL, LH, HH) per level,
coarsest first])``, the layout ``repro.core.dwt2`` returns.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence, Tuple

import numpy as np

# CDF 9/7 (JPEG 2000 Part 1 irreversible, ISO/IEC 15444-1 Annex F,
# Table F.4): alpha, beta, gamma, delta and K.
_A, _B, _G, _D = (-1.586134342059924, -0.052980118572961,
                  0.882911075530934, 0.443506852043971)
_K = 1.230174104914001

#: wavelet -> ((predict, update) pairs, zeta): s *= zeta, d /= zeta
LIFTING = {
    "cdf97": (((_A, _B), (_G, _D)), 1.0 / _K),
    "cdf53": (((-0.5, 0.25),), 1.0),
}

Pyramid = Tuple[np.ndarray, List[Tuple[np.ndarray, np.ndarray, np.ndarray]]]


def _lifting(wavelet: str):
    try:
        return LIFTING[wavelet]
    except KeyError:
        raise KeyError(f"no reference for wavelet {wavelet!r}; "
                       f"available: {sorted(LIFTING)}") from None


#: host threads for the reference (NumPy releases the GIL on large
#: element-wise operations, so row and column blocks run in parallel)
THREADS = min(8, os.cpu_count() or 1)


def _blocks(n: int) -> list:
    step = -(-n // THREADS)
    return [slice(i, min(n, i + step)) for i in range(0, n, step)]


def _parallel(fn, n: int) -> None:
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(fn, b) for b in _blocks(n)]:
            f.result()


def _analyse(x: np.ndarray, axis: int, wavelet: str):
    """One 1-D lifting level along ``axis`` (-1: W, -2: H): (s, d)."""
    pairs, zeta = _lifting(wavelet)
    ev = (slice(0, None, 2),) if axis == -1 else (slice(0, None, 2),
                                                  slice(None))
    od = (slice(1, None, 2),) if axis == -1 else (slice(1, None, 2),
                                                  slice(None))
    s = np.array(x[(Ellipsis,) + ev], np.float64)
    d = np.array(x[(Ellipsis,) + od], np.float64)
    other = -2 if axis == -1 else -1

    def run(blk):
        idx = (Ellipsis, blk) if other == -1 else (Ellipsis, blk,
                                                   slice(None))
        sb, db = s[idx], d[idx]           # views: updated in place
        for p, u in pairs:
            db += p * (sb + np.roll(sb, -1, axis))
            sb += u * (np.roll(db, 1, axis) + db)
        sb *= zeta
        db /= zeta

    _parallel(run, s.shape[other])
    return s, d


def _synthesise(s: np.ndarray, d: np.ndarray, axis: int,
                wavelet: str) -> np.ndarray:
    """Inverse of :func:`_analyse`."""
    pairs, zeta = _lifting(wavelet)
    s = np.array(s, np.float64)
    d = np.array(d, np.float64)
    other = -2 if axis == -1 else -1

    def run(blk):
        idx = (Ellipsis, blk) if other == -1 else (Ellipsis, blk,
                                                   slice(None))
        sb, db = s[idx], d[idx]
        sb /= zeta
        db *= zeta
        for p, u in reversed(pairs):
            sb -= u * (np.roll(db, 1, axis) + db)
            db -= p * (sb + np.roll(sb, -1, axis))

    _parallel(run, s.shape[other])
    shape = list(s.shape)
    shape[axis] *= 2
    out = np.empty(shape, np.float64)
    if axis == -1:
        out[..., 0::2], out[..., 1::2] = s, d
    else:
        out[..., 0::2, :], out[..., 1::2, :] = s, d
    return out


def _level(x: np.ndarray, wavelet: str):
    lo, hi = _analyse(x, -1, wavelet)                  # along W
    ll, lh = _analyse(lo, -2, wavelet)                 # along H
    hl, hh = _analyse(hi, -2, wavelet)
    return ll, (hl, lh, hh)


def _unlevel(ll, detail, wavelet: str) -> np.ndarray:
    hl, lh, hh = detail
    lo = _synthesise(ll, lh, -2, wavelet)
    hi = _synthesise(hl, hh, -2, wavelet)
    return _synthesise(lo, hi, -1, wavelet)


def dwt2(x, wavelet: str, levels: int) -> Pyramid:
    """Forward transform of an image or a batch ``(..., H, W)``."""
    x = np.asarray(x, np.float64)
    h, w = x.shape[-2:]
    if levels < 1 or h % (1 << levels) or w % (1 << levels):
        raise ValueError(f"{h}x{w} does not split {levels} times")
    details = []
    for _ in range(levels):
        x, det = _level(x, wavelet)
        details.append(det)
    return x, details[::-1]


def idwt2(ll, details: Sequence, wavelet: str) -> np.ndarray:
    """Inverse of :func:`dwt2` (details coarsest first)."""
    x = np.asarray(ll, np.float64)
    for det in details:
        x = _unlevel(x, [np.asarray(d, np.float64) for d in det], wavelet)
    return x


def leaves(ll, details) -> list:
    """The subbands of a pyramid in a fixed order: LL, then HL, LH, HH
    of each level, coarsest first."""
    return [ll] + [band for det in details for band in det]
