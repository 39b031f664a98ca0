"""LRU cache of compiled :class:`~repro.engine.plan.DwtPlan` objects.

``get_plan(...)`` is the engine's front door: it normalizes the arguments
into a :class:`~repro.engine.plan.PlanKey` and returns a shared plan,
building one only on a miss.  Hit/miss counters are exposed so callers
(tests, benchmarks) can verify that repeated same-shape traffic pays zero
rebuild cost.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Optional, Tuple

from repro import telemetry as T
from repro.engine.plan import DwtPlan, PlanKey, build_plan

# process-wide cache traffic (every PlanCache instance records here;
# instances also keep their own hit/miss ints for isolated .stats())
CACHE_LOOKUPS = T.counter(
    "repro_plan_cache_lookups_total",
    "plan-cache lookups by result", labelnames=("result", "backend"))


class PlanCache:
    """Thread-safe LRU mapping PlanKey -> DwtPlan with hit/miss counters."""

    def __init__(self, maxsize: int = 128):
        self.maxsize = maxsize
        self._plans: "OrderedDict[PlanKey, DwtPlan]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: PlanKey,
            builder: Callable[[PlanKey], DwtPlan] = build_plan) -> DwtPlan:
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
                self.hits += 1
                CACHE_LOOKUPS.inc(result="hit", backend=key.backend)
                return plan
        # build outside the lock: scheme algebra + jit wrapping can be slow
        with T.span("plan.cache_miss", backend=key.backend, fuse=key.fuse,
                    scheme=key.scheme):
            plan = builder(key)
        with self._lock:
            if key in self._plans:      # racing builder won; reuse theirs
                self.hits += 1
                CACHE_LOOKUPS.inc(result="hit", backend=key.backend)
                return self._plans[key]
            self.misses += 1
            CACHE_LOOKUPS.inc(result="miss", backend=key.backend)
            self._plans[key] = plan
            while len(self._plans) > self.maxsize:
                self._plans.popitem(last=False)
            return plan

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key: PlanKey) -> bool:
        return key in self._plans

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "size": len(self._plans), "maxsize": self.maxsize}

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self.hits = 0
            self.misses = 0


_GLOBAL = PlanCache()


def global_cache() -> PlanCache:
    return _GLOBAL


def get_plan(*, wavelet: str = "cdf97", scheme: str = "ns-polyconv",
             levels: int = 1, shape: Tuple[int, ...], dtype: str = "float32",
             backend: str = "jnp", optimize: bool = False,
             fuse: str = "none", boundary: str = "periodic",
             compute_dtype: str = "float32", tap_opt: str = "full",
             tiles: Optional[Tuple[int, int]] = None,
             packet=None, ndim: int = 2,
             bit_depth: Optional[int] = None,
             cache: Optional[PlanCache] = None) -> DwtPlan:
    """Fetch (or build) the plan for one transform configuration.

    The engine's front door: normalizes the arguments into a
    :class:`~repro.engine.plan.PlanKey` and returns the shared
    :class:`~repro.engine.plan.DwtPlan`, building one only on a miss.
    ``cache=None`` uses the process-global LRU; pass an explicit
    :class:`PlanCache` for isolation (tests, autotuning sweeps).

    ``packet`` accepts anything
    :meth:`repro.core.packets.PacketTree.from_spec` does (a PacketTree,
    ``"full:D"`` / ``"dwt:L"``, or leaf paths); it is normalized to the
    canonical leaf tuple — so every admissible spelling of the same
    tree shares one cached plan — and ``levels`` is overridden by the
    tree depth.  ``ndim=3`` keys the t+2D volume transform over
    ``(..., T, H, W)``.  ``bit_depth`` is the precision of integer
    samples (``dtype`` unsigned), which the forward level-shifts.

    >>> from repro.engine import PlanCache, get_plan
    >>> cache = PlanCache()
    >>> plan = get_plan(shape=(8, 64, 64), levels=2, scheme="ns-polyconv",
    ...                 backend="xla", fuse="none", cache=cache)
    >>> plan.num_steps                  # 2 barrier steps/level x 2 levels
    4
    >>> plan.pallas_calls               # xla: one grouped conv per step
    4
    >>> plan.backend.name
    'xla'
    >>> get_plan(shape=(8, 64, 64), levels=2, scheme="ns-polyconv",
    ...          backend="xla", fuse="none", cache=cache) is plan
    True
    >>> cache.stats()["hits"], cache.stats()["misses"]
    (1, 1)
    >>> pk = get_plan(shape=(64, 64), packet="full:2", cache=cache)
    >>> pk.key.levels, len(pk.key.packet)     # depth-2 full tree
    (2, 16)
    >>> get_plan(shape=(64, 64),              # same tree, spelled out
    ...          packet=pk.key.packet, cache=cache) is pk
    True
    """
    if packet is not None:
        from repro.core import packets as PK
        tree = PK.PacketTree.from_spec(packet)
        packet = tree.leaves
        levels = tree.depth
    key = PlanKey(wavelet=wavelet, scheme=scheme, levels=int(levels),
                  shape=tuple(int(d) for d in shape), dtype=str(dtype),
                  backend=backend, optimize=bool(optimize), fuse=fuse,
                  boundary=boundary, compute_dtype=str(compute_dtype),
                  tap_opt=tap_opt,
                  tiles=(None if tiles is None
                         else (int(tiles[0]), int(tiles[1]))),
                  packet=packet, ndim=int(ndim),
                  bit_depth=None if bit_depth is None else int(bit_depth))
    # explicit None check: an empty PlanCache is falsy (__len__ == 0)
    return (_GLOBAL if cache is None else cache).get(key)


def plan_cache_stats() -> dict:
    return _GLOBAL.stats()


def clear_plan_cache() -> None:
    _GLOBAL.clear()


# zeroed section schemas: engine.stats() keeps its exact shape even
# when a subsystem fails to import or errors at read time (a stats call
# must never take a dashboard scrape down with it)
_SERVE_ZERO = {
    "submitted": 0, "served": 0, "failed": 0, "rejected": 0,
    "redispatched": 0, "worker_deaths": 0, "workers_spawned": 0,
    "deadline_exceeded": 0, "quarantined": 0, "breaker_rejections": 0,
    "batches": 0, "padded_images": 0, "mean_occupancy": None,
    "latency_samples": 0, "latency_dropped": 0,
    "p50_ms": None, "p99_ms": None, "img_per_s": None,
}
_AUTO_ZERO = {"predictions": 0, "store_hits": 0, "cold_fallbacks": 0,
              "choices": {}}
_PYRAMID_ZERO = {"pyramid_kernel_launches": 0, "vmem_fallbacks": 0}
_TELEMETRY_ZERO = {"mode": "off", "metrics": 0, "series": 0,
                   "dropped_series": 0,
                   "spans": {"recorded": 0, "resident": 0, "dropped": 0,
                             "capacity": 0}}
_FAULTS_ZERO = {"active": False, "injections": 0, "enabled": True,
                "fallbacks": 0, "retries": 0}


def _section(zero: dict, read) -> dict:
    """One stats() section, degrading to its zeroed schema on failure
    (missing keys are filled in; extras from the live read survive)."""
    try:
        live = read()
    except Exception:
        return dict(zero)
    out = dict(zero)
    out.update(live)
    return out


def stats() -> dict:
    """Engine-wide observability summary: plan-cache hit/miss counters,
    fused-pyramid counters (kernel launches, VMEM-budget fallbacks),
    auto-backend counters (cost-model predictions, store hits,
    cold-start fallbacks, chosen-config histogram), block-table
    device-mismatch fallbacks, the registered-backend capability matrix,
    serving-runtime counters (p50/p99 request latency, served img/s,
    batch occupancy, backpressure/re-dispatch counts — see
    :mod:`repro.serve`), the telemetry registry/span-ring accounting
    (:mod:`repro.telemetry`), plus one row per cached plan (steps,
    kernel launches, compiled tap-program op counts, tile counts,
    pyramid window geometry, the auto-resolved choice) — what
    benchmarks and production dashboards need to see at a glance.

    Every counter is a view over the central telemetry registry; the
    ``serve`` / ``auto`` / ``pyramid`` / ``telemetry`` sections keep a
    stable (zeroed) schema even if their subsystem fails to load.

    >>> from repro import engine
    >>> s = engine.stats()
    >>> sorted(s)
    ['auto', 'backends', 'block_table', 'faults', 'plan_cache', 'plans', 'pyramid', 'serve', 'telemetry']
    >>> sorted(s['faults'])[:3]          # repro.faults plane + policies
    ['active', 'enabled', 'fallbacks']
    >>> sorted(k for k in s['serve'] if k.startswith('p'))
    ['p50_ms', 'p99_ms', 'padded_images']
    >>> [row["backend"] for row in s["backends"]]
    ['auto', 'jnp', 'pallas', 'xla']
    >>> sorted(s["auto"])
    ['choices', 'cold_fallbacks', 'predictions', 'store_hits']
    >>> sorted(s["telemetry"])
    ['dropped_series', 'metrics', 'mode', 'series', 'spans']
    """
    from repro.engine import autotune as AT
    from repro.engine import backends as B
    from repro.engine import plan as P
    with _GLOBAL._lock:
        items = list(_GLOBAL._plans.items())
    plans = []
    for key, plan in items:
        row = {"wavelet": key.wavelet, "scheme": key.scheme,
               "levels": key.levels, "shape": key.shape,
               "backend": key.backend, "fuse": key.fuse,
               "optimize": key.optimize, "tap_opt": key.tap_opt,
               "num_steps": plan.num_steps,
               "pallas_calls": plan.pallas_calls}
        compiled = plan.compiled_stats()
        if compiled is not None:
            row["compiled_macs"] = compiled["macs"]
            row["compiled_nodes"] = compiled["nodes"]
            row["compiled_halo"] = compiled["halo"]
        if plan.grid is not None:
            row["tiles"] = key.tiles
            row["tile_count"] = plan.tile_count
            row["tile_grid"] = plan.grid.grid_shape
            row["halo_margin"] = plan.grid.margin
        if plan.pyramid is not None:
            row["pyramid_block"] = plan.pyramid.block
            row["pyramid_window"] = plan.pyramid.window_shape
            row["pyramid_vmem_bytes"] = plan.pyramid.vmem_bytes
        if key.packet is not None:
            row["packet_leaves"] = len(key.packet)
            row["packet_depth"] = key.levels
        if key.ndim != 2:
            row["ndim"] = key.ndim
        if plan.fallback is not None:
            row["fallback"] = plan.fallback
        if plan.auto is not None:
            # the cache key says backend="auto"; the plan key carries the
            # concrete resolution the cost model picked
            row["auto"] = {"backend": plan.key.backend,
                           "fuse": plan.key.fuse,
                           "tap_opt": plan.key.tap_opt,
                           "source": plan.auto.source,
                           "predicted_s": plan.auto.predicted_s}
        plans.append(row)

    def _auto():
        from repro.profiler import auto as PA
        return PA.auto_stats()

    def _serve():
        from repro.serve import metrics as SM
        return SM.serve_stats()

    def _faults():
        from repro import faults as F
        return F.stats()

    return {"plan_cache": _GLOBAL.stats(),
            "faults": _section(_FAULTS_ZERO, _faults),
            "pyramid": _section(_PYRAMID_ZERO, lambda: dict(P.COUNTERS)),
            "auto": _section(_AUTO_ZERO, _auto),
            "block_table": _section(
                {"device_fallbacks": 0, "path": ""},
                lambda: {"device_fallbacks": AT.COUNTERS[
                    "device_fallbacks"], "path": str(AT.table_path())}),
            "backends": list(B.capability_matrix()),
            "serve": _section(_SERVE_ZERO, _serve),
            "telemetry": _section(_TELEMETRY_ZERO, T.stats),
            "plans": plans}
