"""Efficiency attribution: join measured time with the analytic models.

The engine already models, per configuration, exactly the two
quantities the paper's measurement argument is built on — HBM bytes
moved (``scheme_hbm_bytes`` / ``pyramid_hbm_bytes`` via
:func:`repro.profiler.model.config_features`) and in-kernel MACs (the
compiled tap programs).  This module divides measured wall-clock by
them and publishes the quotients as gauges:

* ``repro_achieved_gbps``       — modeled bytes / measured seconds,
* ``repro_achieved_macs_per_s`` — compiled MACs / measured seconds,
* ``repro_measured_seconds``    — the raw measurement,
* ``repro_model_time_ratio``    — measured / cost-model-predicted time
  (only for plans resolved through ``backend="auto"``, whose
  :class:`~repro.profiler.auto.AutoChoice` carries a prediction),

all labeled ``(scheme, backend, fuse, levels, op)`` — a live roofline
per plan, the measured-vs-modeled comparison the profiler's CostModel
previously did blind.

One caller feeds it: :func:`repro.profiler.trace.profile_plan`, which
times with ``block_until_ready`` around the median of reps.  The
``execute.*`` spans do not: on an async backend they time the enqueue,
not the device.  Attribution inputs are computed once per plan and
cached on the plan object.
"""
from __future__ import annotations

from typing import Optional

from repro.telemetry.config import CONFIG
from repro.telemetry.registry import REGISTRY

_ACHIEVED_GBPS = REGISTRY.gauge(
    "repro_achieved_gbps",
    "modeled HBM GB moved / measured second, per plan (live roofline)")
_ACHIEVED_MACS = REGISTRY.gauge(
    "repro_achieved_macs_per_s",
    "compiled tap-program MACs / measured second, per plan")
_MEASURED_S = REGISTRY.gauge(
    "repro_measured_seconds",
    "last measured wall-clock seconds per execution, per plan")
_MODEL_RATIO = REGISTRY.gauge(
    "repro_model_time_ratio",
    "measured / cost-model-predicted seconds (auto-resolved plans)")


def plan_macs(plan) -> Optional[int]:
    """Total compiled MACs of one full forward execution (all levels,
    batch included), or None when ``tap_opt="off"`` (no compiled
    programs to count)."""
    from repro import compiler as C
    batch = 1
    for d in plan.key.shape[:-2]:
        batch *= int(d)
    total = 0
    for spec in plan.level_specs:
        if spec.fwd_programs is None:
            return None
        st = C.program_stats(spec.fwd_programs)
        hp, wp = spec.plane_shape
        # program MACs are per polyphase position (4 output samples)
        total += st["macs"] * hp * wp
    return total * batch


def plan_cost_inputs(plan) -> Optional[dict]:
    """Analytic attribution inputs of one plan — modeled HBM bytes,
    modeled launches, compiled MACs — computed once and cached on the
    plan object (attribution runs per execution; the models must not)."""
    cached = getattr(plan, "_attr_inputs", None)
    if cached is not None:
        return cached or None       # {} sentinel = "tried, failed"
    try:
        from repro.profiler.model import config_features
        feats = config_features(plan.key)
        inputs = {"hbm_bytes": feats["hbm_bytes"],
                  "launches": feats["launches"],
                  "macs": plan_macs(plan)}
    except Exception:
        # attribution is best-effort observability: a key the analytic
        # models cannot featurize must not take execution down
        plan._attr_inputs = {}
        return None
    plan._attr_inputs = inputs
    return inputs


def _labels(plan, op: str) -> dict:
    k = plan.key
    return {"scheme": k.scheme, "backend": k.backend, "fuse": k.fuse,
            "levels": k.levels, "op": op}


def record_execution(plan, seconds: float, op: str = "forward"
                     ) -> Optional[dict]:
    """Publish achieved-GB/s / achieved-MACs/s gauges for one measured
    execution of ``plan``; returns the attribution row (or None when
    telemetry is off, the measurement is unusable, or the plan cannot
    be featurized)."""
    if not CONFIG.counters_on or not seconds or seconds <= 0:
        return None
    inputs = plan_cost_inputs(plan)
    if inputs is None:
        return None
    labels = _labels(plan, op)
    row = {**labels, "seconds": seconds,
           "hbm_bytes": inputs["hbm_bytes"],
           "macs": inputs["macs"],
           "gbps": inputs["hbm_bytes"] / seconds / 1e9,
           "macs_per_s": (inputs["macs"] / seconds
                          if inputs["macs"] is not None else None)}
    _MEASURED_S.set(seconds, **labels)
    _ACHIEVED_GBPS.set(row["gbps"], **labels)
    if row["macs_per_s"] is not None:
        _ACHIEVED_MACS.set(row["macs_per_s"], **labels)
    predicted = getattr(getattr(plan, "auto", None), "predicted_s", None)
    if predicted:
        row["model_time_ratio"] = seconds / predicted
        _MODEL_RATIO.set(row["model_time_ratio"], **labels)
    return row


def roofline() -> list:
    """Current attribution rows, one per (plan-config, op) series that
    has recorded: the live measured-vs-modeled table for dashboards and
    ``benchmarks/run.py``."""
    out = {}
    for metric, field in ((_MEASURED_S, "seconds"),
                          (_ACHIEVED_GBPS, "gbps"),
                          (_ACHIEVED_MACS, "macs_per_s"),
                          (_MODEL_RATIO, "model_time_ratio")):
        for s in metric.series():
            key = tuple(sorted(s["labels"].items()))
            out.setdefault(key, dict(s["labels"]))[field] = s["value"]
    return [out[k] for k in sorted(out)]
