"""``backend="auto"``: profile-guided plan selection.

The paper's central empirical finding is that no single calculation
scheme — and, on the follow-up GPU study, no single execution strategy
— wins everywhere: the winner flips with device, image size, and
scheme.  :func:`choose` turns that finding into engine behavior.  At
plan build, a ``PlanKey`` with ``backend="auto"`` is resolved to a
concrete ``(backend, fuse, block_target, tap_opt)`` by, in order:

1. **store hit** — an exact measured record of this configuration on
   this device picks the fastest measured candidate directly;
2. **model prediction** — the fitted cost model
   (:class:`~repro.profiler.model.CostModel`) predicts wall-clock for
   every valid candidate from its analytic features (modeled HBM bytes
   + launches) and nearest measured neighbors;
3. **cold-start heuristic** — with an empty store, a deterministic
   platform rule: TPU -> pallas/"levels", GPU -> xla/"levels",
   anything else -> jnp/"levels".

Every resolution is counted on the telemetry registry
(:data:`RESOLUTIONS`, labeled by source) and the chosen configs
histogrammed (:data:`CHOICES`) — surfaced through
``repro.engine.stats()["auto"]`` and printed by ``benchmarks/run.py``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from repro import telemetry as T
from repro.profiler import model as M
from repro.profiler import store as ST

RESOLUTIONS = T.counter(
    "repro_auto_resolutions_total",
    'backend="auto" resolutions by source (store hit / model prediction '
    "/ cold-start heuristic)", labelnames=("source",))
CHOICES = T.counter(
    "repro_auto_choices_total",
    'concrete configurations backend="auto" resolved to',
    labelnames=("backend", "fuse"))

#: deprecated dict-style alias of the pre-telemetry counters (legacy
#: key -> labeled registry series); removed one release after PR 8
AUTO_COUNTERS = T.CounterAlias({
    "predictions": ("repro_auto_resolutions_total", {"source": "model"}),
    "store_hits": ("repro_auto_resolutions_total", {"source": "store"}),
    "cold_fallbacks": ("repro_auto_resolutions_total",
                       {"source": "heuristic"}),
})


def reset_counters() -> None:
    RESOLUTIONS.reset()
    CHOICES.reset()


def auto_stats() -> dict:
    """Counters consumed by ``engine.stats()`` / ``benchmarks/run.py``:
    resolutions served by model predictions, by exact store hits, by the
    cold-start heuristic, and the chosen-config histogram."""
    choices = {f'{s["labels"]["backend"]}|{s["labels"]["fuse"]}':
               int(s["value"]) for s in CHOICES.series()}
    return {**dict(AUTO_COUNTERS.items()),
            "choices": dict(sorted(choices.items()))}


@dataclasses.dataclass(frozen=True)
class AutoChoice:
    """The concrete configuration ``backend="auto"`` resolved to."""

    backend: str
    fuse: str
    tap_opt: str
    block: Optional[Tuple[int, int]]   # block target (None = table/default)
    source: str                        # "store" | "model" | "heuristic"
    predicted_s: Optional[float]       # measured (store) / predicted time


def enumerate_candidates(key) -> List[Tuple[str, str, str]]:
    """Every ``(backend, fuse, tap_opt)`` the registry can execute for
    this key (the choice space).  ``tap_opt`` candidates are pinned to
    "full" — the compiled programs' measured best (PR 2) — but the store
    can still teach :func:`choose` a different level via exact records
    (e.g. written by a hand-driven sweep)."""
    from repro.engine import backends as B
    cands = []
    for name in B.available_backends():
        if name == "auto":
            continue
        bk = B.get_backend(name)
        for fuse in bk.fuse_modes:
            trial = dataclasses.replace(key, backend=name, fuse=fuse,
                                        tap_opt="full")
            try:
                bk.validate(trial)
            except ValueError:
                continue
            cands.append((name, fuse, "full"))
    return cands


def _heuristic(key) -> AutoChoice:
    """Deterministic cold-start rule keyed on the platform: prefer the
    backend/fuse pair the measured PRs showed fastest there."""
    import jax
    from repro.engine import backends as B
    platform = jax.devices()[0].platform
    prefs = {"tpu": [("pallas", "levels")],
             "gpu": [("xla", "levels")]}.get(platform, [])
    prefs += [("jnp", "levels"), ("jnp", "none")]
    for name, fuse in prefs:
        try:
            B.get_backend(name).validate(
                dataclasses.replace(key, backend=name, fuse=fuse,
                                    tap_opt="full"))
        except ValueError:
            continue
        return AutoChoice(backend=name, fuse=fuse, tap_opt="full",
                          block=None, source="heuristic", predicted_s=None)
    raise ValueError(f"no registered backend can execute {key}")


def choose(key, store: Optional[ST.TraceStore] = None,
           block_target: Optional[Tuple[int, int]] = None) -> AutoChoice:
    """Resolve a ``backend="auto"`` key to a concrete configuration.

    Asks the persistent store first (exact measured records of this
    configuration on this device), then the fitted cost model, then the
    cold-start heuristic.  ``block_target`` (an explicit caller
    override) only suppresses the store's block annotation — the
    concrete plan build applies it either way.
    """
    from repro.engine import autotune as AT
    if store is None:
        store = ST.TraceStore()
    fingerprint = AT.device_fingerprint()
    device_recs = store.records(fingerprint)
    exact = [r for r in device_recs if r.matches_key(key)]
    cands = enumerate_candidates(key)
    model = M.CostModel.fit(device_recs) if device_recs else None

    best = None         # (time_s, backend, fuse, tap_opt, block, source)
    for backend, fuse, tap_opt in cands:
        matches = [r for r in exact
                   if r.backend == backend and r.fuse == fuse]
        if matches:
            rec = min(matches, key=lambda r: (r.time_s, r.tap_opt))
            row = (rec.time_s, backend, fuse, rec.tap_opt, rec.block,
                   "store")
        elif model is not None:
            feats = M.config_features(key, backend=backend, fuse=fuse,
                                      tap_opt=tap_opt)
            t = model.predict(backend, fuse, feats["hbm_bytes"],
                              feats["launches"])
            if t is None:
                continue
            row = (t, backend, fuse, tap_opt, None, "model")
        else:
            continue
        if best is None or row[:3] < best[:3]:
            best = row

    if best is None:
        RESOLUTIONS.inc(source="heuristic")
        choice = _heuristic(key)
    else:
        t, backend, fuse, tap_opt, block, source = best
        RESOLUTIONS.inc(source=source)
        if block_target is not None:
            block = None
        if block is None:
            block = AT.lookup(key.scheme, key.shape[-2:], fuse, backend)
        choice = AutoChoice(backend=backend, fuse=fuse, tap_opt=tap_opt,
                            block=block, source=source, predicted_s=t)
    CHOICES.inc(backend=choice.backend, fuse=choice.fuse)
    return choice
