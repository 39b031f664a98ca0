"""Tiled transform API: ``dwt2_tiled`` / ``idwt2_tiled`` + plan executors.

A tiled plan is a thin orchestration layer over the monolithic engine:
the grid planner (:mod:`repro.tiling.grid`) derives exact halo margins
from the plan's compiled tap programs, the exchange layer
(:mod:`repro.tiling.exchange`) materializes ``core + halo`` windows, and
every window then runs through an ordinary *monolithic* window plan —
fetched from the same LRU plan cache, with the tile axis stacked onto
the batch dims so the whole grid is one batched execution.  The window
plan inherits the fuse mode, so ``fuse="pyramid"`` runs every tile
window through the fused-pyramid megakernel: the entire tiled
multi-level transform is a single ``pallas_call``.  Because the
window transform executes the very same compiled programs elementwise,
tile cores are bit-identical to the monolithic transform at
``tap_opt="off"``/``"exact"`` (and equal to fp tolerance at ``"full"``).

Transports:

* ``"gather"`` (default) — in-core, any batch shape, any tile size
  (non-dividing tiles wrap harmlessly); plans cache under ``PlanKey``
  with the ``tiles`` field set, so ``dwt2(..., tiles=...)`` traffic pays
  zero rebuild cost exactly like monolithic traffic.
* ``"shard_map"`` — the image lives sharded one tile per device over a
  2-D mesh; halos move by ppermute neighbor exchange and each device
  transforms only its own window.  Requires an evenly-dividing grid
  matching the mesh and single-hop margins (margin <= tile edge).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import telemetry as T
from repro.engine.pyramid import Pyramid
from repro.faults import inject as FI
from repro.tiling import exchange as EX


def _window_plan(key, shape):
    """Monolithic plan for the stacked tile windows, via the plan cache."""
    from repro import engine as E  # deferred: engine <-> tiling cycle
    return E.get_plan(wavelet=key.wavelet, scheme=key.scheme,
                      levels=key.levels, shape=shape, dtype=key.dtype,
                      backend=key.backend, optimize=key.optimize,
                      fuse=key.fuse, boundary=key.boundary,
                      compute_dtype=key.compute_dtype, tap_opt=key.tap_opt)


def make_tiled_forward(plan):
    """Forward executor of a tiled plan: gather windows -> batched window
    transform -> stitch per-level cores."""
    key, grid = plan.key, plan.grid
    levels = key.levels
    batch = key.shape[:-2]
    wplan = _window_plan(key, batch + (grid.count,) + grid.window_shape)

    def run(x):
        # spans no-op inside jit tracing (fuse="levels"); on the eager
        # paths they time gather / transform / stitch separately.  The
        # fault site likewise fires per call eagerly, once at trace
        # time under jit (python-level hook, like the spans)
        with T.span("tile.halo_gather", op="forward", tiles=grid.count):
            FI.maybe_inject("tiling.halo_gather", op="forward",
                            tiles=grid.count)
            wins = EX.gather_windows(x, grid)
        with T.span("tile.window_transform", op="forward",
                    tiles=grid.count, backend=key.backend):
            wll, wdetails = wplan._forward(wins)
        with T.span("tile.stitch", op="forward", tiles=grid.count):
            ll = EX.stitch_plane(wll, grid, levels - 1)
            details = tuple(
                tuple(EX.stitch_plane(d, grid, levels - 1 - k)
                      for d in det)
                for k, det in enumerate(wdetails))
        return ll, details

    return jax.jit(run) if key.fuse == "levels" else run


def make_tiled_inverse(plan):
    """Inverse executor of a tiled plan: gather per-level subband windows
    (inverse margins) -> batched window inverse -> stitch image cores."""
    key, grid = plan.key, plan.grid
    levels = key.levels
    batch = key.shape[:-2]
    wplan = _window_plan(key, batch + (grid.count,) + grid.inv_window_shape)

    def run(ll, details):
        with T.span("tile.halo_gather", op="inverse", tiles=grid.count):
            FI.maybe_inject("tiling.halo_gather", op="inverse",
                            tiles=grid.count)
            wll = EX.gather_plane_windows(ll, grid, levels - 1)
            wdet = tuple(
                tuple(EX.gather_plane_windows(d, grid, levels - 1 - k)
                      for d in det)
                for k, det in enumerate(details))
        with T.span("tile.window_transform", op="inverse",
                    tiles=grid.count, backend=key.backend):
            xw = wplan._inverse(wll, wdet)
        with T.span("tile.stitch", op="inverse", tiles=grid.count):
            return EX.stitch_plane(xw, grid, 0, inverse=True)

    return jax.jit(run) if key.fuse == "levels" else run


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def dwt2_tiled(x: jax.Array, wavelet: str = "cdf97", levels: int = 1,
               scheme: str = "ns-polyconv", *,
               tiles: Tuple[int, int] = (256, 256),
               optimize: bool = False, backend: str = "jnp",
               fuse: str = "none", boundary: str = "periodic",
               compute_dtype: str = "float32", tap_opt: str = "full",
               transport: str = "gather", mesh=None,
               mesh_axes: Tuple[str, str] = ("tr", "tc")) -> Pyramid:
    """Forward 2-D DWT over a grid of ``tiles``-sized halo-padded tiles.

    Equivalent to ``dwt2(x, ..., tiles=tiles)`` for the default gather
    transport; ``transport="shard_map"`` instead runs one tile per device
    of ``mesh`` (axes ``mesh_axes`` sized like the tile grid).  Tile
    cores match the monolithic transform samplewise (bit-identically on
    the eager jnp path), including non-dividing tile sizes.

    >>> import jax.numpy as jnp
    >>> from repro.core import dwt2
    >>> from repro.tiling import dwt2_tiled
    >>> x = jnp.arange(64.0 * 64).reshape(64, 64)
    >>> tiled = dwt2_tiled(x, wavelet="cdf97", levels=2, tiles=(32, 32))
    >>> mono = dwt2(x, wavelet="cdf97", levels=2)
    >>> tiled.ll.shape
    (16, 16)
    >>> bool(jnp.allclose(tiled.ll, mono.ll, atol=1e-3))
    True
    """
    x = jnp.asarray(x)
    if transport == "gather":
        from repro.core import transform as T
        return T.dwt2(x, wavelet=wavelet, levels=levels, scheme=scheme,
                      optimize=optimize, backend=backend, fuse=fuse,
                      boundary=boundary, compute_dtype=compute_dtype,
                      tap_opt=tap_opt, tiles=tiles)
    if transport != "shard_map":
        raise ValueError(f"unknown transport {transport!r}; "
                         f"available: ('gather', 'shard_map')")
    return _dwt2_shard_map(x, wavelet, levels, scheme, tiles, optimize,
                           backend, fuse, boundary, compute_dtype, tap_opt,
                           mesh, mesh_axes)


def idwt2_tiled(pyr: Pyramid, wavelet: str = "cdf97",
                scheme: str = "ns-polyconv", *,
                tiles: Tuple[int, int] = (256, 256),
                optimize: bool = False, backend: str = "jnp",
                fuse: str = "none", boundary: str = "periodic",
                compute_dtype: str = "float32", tap_opt: str = "full",
                transport: str = "gather", mesh=None,
                mesh_axes: Tuple[str, str] = ("tr", "tc")) -> jax.Array:
    """Inverse of :func:`dwt2_tiled` (shares its plan through the cache)."""
    levels = pyr.levels
    if transport == "gather":
        from repro.core import transform as T
        return T.idwt2(pyr, wavelet=wavelet, scheme=scheme,
                       optimize=optimize, backend=backend, fuse=fuse,
                       boundary=boundary, compute_dtype=compute_dtype,
                       tap_opt=tap_opt, tiles=tiles)
    if transport != "shard_map":
        raise ValueError(f"unknown transport {transport!r}; "
                         f"available: ('gather', 'shard_map')")
    return _idwt2_shard_map(pyr, wavelet, levels, scheme, tiles, optimize,
                            backend, fuse, boundary, compute_dtype, tap_opt,
                            mesh, mesh_axes)


# ---------------------------------------------------------------------------
# shard_map transport (cross-device)
# ---------------------------------------------------------------------------

def _shard_setup(shape, dtype, wavelet, levels, scheme, tiles, optimize,
                 backend, fuse, boundary, compute_dtype, tap_opt, mesh,
                 mesh_axes, inverse: bool):
    from repro import engine as E
    if mesh is None:
        raise ValueError("transport='shard_map' requires a mesh (2-D device "
                         "mesh with axes sized like the tile grid)")
    if len(shape) != 2:
        raise ValueError(f"shard_map transport shards single (H, W) images "
                         f"over the mesh, got shape {shape}")
    plan = E.get_plan(wavelet=wavelet, scheme=scheme, levels=levels,
                      shape=tuple(shape), dtype=str(dtype), backend=backend,
                      optimize=optimize, fuse=fuse, boundary=boundary,
                      compute_dtype=compute_dtype, tap_opt=tap_opt,
                      tiles=tiles)
    grid = plan.grid
    EX.validate_shard_grid(grid, mesh, mesh_axes, inverse=inverse)
    wshape = grid.inv_window_shape if inverse else grid.window_shape
    wplan = _window_plan(plan.key, wshape)
    return grid, wplan


def _dwt2_shard_map(x, wavelet, levels, scheme, tiles, optimize, backend,
                    fuse, boundary, compute_dtype, tap_opt, mesh, mesh_axes):
    from jax.sharding import NamedSharding, PartitionSpec as P
    grid, wplan = _shard_setup(
        x.shape, x.dtype, wavelet, levels, scheme, tiles, optimize, backend,
        fuse, boundary, compute_dtype, tap_opt, mesh, mesh_axes, False)
    nrc = grid.grid_shape
    ra, ca = mesh_axes
    spec = P(ra, ca)

    def per_shard(block):
        win = EX.shard_halo_pad(block, grid.margin, ra, ca, nrc)
        wll, wdetails = wplan._forward(win)
        ll = EX.extract_core(wll, grid, levels - 1)
        details = tuple(
            tuple(EX.extract_core(d, grid, levels - 1 - k) for d in det)
            for k, det in enumerate(wdetails))
        return ll, details

    out_specs = (spec, tuple((spec, spec, spec) for _ in range(levels)))
    f = jax.shard_map(per_shard, mesh=mesh, in_specs=spec,
                      out_specs=out_specs, check_vma=False)
    x = jax.device_put(x, NamedSharding(mesh, spec))
    ll, details = f(x)
    return Pyramid(ll=ll, details=list(details))


def _idwt2_shard_map(pyr, wavelet, levels, scheme, tiles, optimize, backend,
                     fuse, boundary, compute_dtype, tap_opt, mesh,
                     mesh_axes):
    from jax.sharding import NamedSharding, PartitionSpec as P
    ll = jnp.asarray(pyr.ll)
    shape = (ll.shape[-2] << levels, ll.shape[-1] << levels)
    grid, wplan = _shard_setup(
        shape, ll.dtype, wavelet, levels, scheme, tiles, optimize, backend,
        fuse, boundary, compute_dtype, tap_opt, mesh, mesh_axes, True)
    (th, tw), nrc = grid.tile, grid.grid_shape
    mi = grid.inv_margin
    ra, ca = mesh_axes
    spec = P(ra, ca)

    def per_shard(llb, detb):
        wll = EX.shard_halo_pad(llb, mi >> levels, ra, ca, nrc)
        wdet = tuple(
            tuple(EX.shard_halo_pad(d, mi >> (levels - k), ra, ca, nrc)
                  for d in det)
            for k, det in enumerate(detb))
        xw = wplan._inverse(wll, wdet)
        return xw[mi:mi + th, mi:mi + tw]

    in_specs = (spec, tuple((spec, spec, spec) for _ in range(levels)))
    f = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                      out_specs=spec, check_vma=False)
    sh = NamedSharding(mesh, spec)
    ll = jax.device_put(ll, sh)
    details = tuple(tuple(jax.device_put(jnp.asarray(d), sh) for d in det)
                    for det in pyr.details)
    return f(ll, details)
