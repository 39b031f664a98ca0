"""Device time by program layer, from the scopes the program recorded.

Under ``REPRO_TELEMETRY=spans`` (every traced run) the program keeps,
for each executable it compiled, which ``dwt.*`` scope each HLO
instruction ran under (``repro.telemetry.op_scopes()``: ``{module:
{instruction: scope}}``).  A device op of the trace is named by its
instruction, so its time goes to that instruction's scope:

- :func:`scope_seconds` does that for the op times of a
  :class:`bench.trace.Summary` (what the per-layer readers get), for a
  window that runs one executable, as each cell's does;
- :func:`reduce` does it from records that keep each device's
  ``XLA Modules`` events, so an op is looked up in the executable that
  ran it, and adds the program's own spans, the idle gaps labelled by
  the innermost annotation, and the host threads busy in each stall
  (``bench/scope_report.py`` prints it).

A program without the map (an older commit, or spans off) gives None.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as TR

UNSCOPED = "unscoped"
MODULE_LINE = "XLA Modules"
#: the program's spans whose durations are reported
PROGRAM_SPANS = ("execute.", "plan.")
#: a gap at least this long is a stall, and its host threads are listed
STALL_S = 0.010


def module_scopes(module: str) -> Optional[Dict[str, str]]:
    """``{instruction: scope}`` of executable ``module`` as the program
    recorded it, or None where it recorded none."""
    try:
        from repro import telemetry
        return telemetry.op_scopes().get(module) or None
    except (ImportError, AttributeError):
        return None


def instruction(op: str) -> str:
    """The HLO instruction name at the head of an op label."""
    return op.split(" ", 1)[0]


def scope_seconds(op_s: Dict[str, float], scopes: Dict[str, str]
                  ) -> Dict[str, float]:
    """Seconds per scope of ``op_s`` (op label -> seconds); an op the
    map does not know goes to ``"unscoped"``."""
    out: Dict[str, float] = defaultdict(float)
    for op, s in op_s.items():
        out[scopes.get(instruction(op), UNSCOPED)] += s
    return dict(out)


def share(ctx, module: str, scope: str) -> Optional[float]:
    """Percent of device busy time in ``scope`` of executable
    ``module`` over the traced window."""
    t = ctx.trace
    scopes = module_scopes(module)
    if t is None or not t.busy_s or scopes is None:
        return None
    return 100.0 * scope_seconds(t.op_s, scopes).get(scope, 0.0) / t.busy_s


# -- the module-aware reduction ---------------------------------------------

def module_name(event: str) -> str:
    """``jit_dwt_forward(123...)`` -> ``jit_dwt_forward``."""
    return re.sub(r"\(\d+\)$", "", event)


def load(trace_dir) -> dict:
    """What :func:`bench.trace.load` leaves out of the same trace: each
    TPU's module events (``"modules"``: ``{id: [[module, start_ns,
    end_ns], ...]}``) and every host event of every thread
    (``"threads"``: ``[[thread, name, start_ns, end_ns], ...]``), the
    TPU runtime's included."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    modules: Dict[str, list] = {}
    threads: list = []
    for plane in data.planes:
        m = TR.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULE_LINE:
                modules.setdefault(m.group(1), []).extend(
                    [module_name(e.name), int(e.start_ns), int(e.end_ns)]
                    for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                threads.extend([line.name, e.name, int(e.start_ns),
                                int(e.end_ns)] for e in line.events)
    return {"modules": {str(k): v for k, v in modules.items()},
            "threads": threads}


def label(gap: Tuple[int, int], host: Sequence[Tuple[str, int, int]]
          ) -> str:
    """As :func:`bench.trace.label`, but of annotations that overlap the
    gap equally the shortest (innermost) wins: a gap inside
    ``execute.inverse`` inside ``bench.decode`` is ``execute.inverse``."""
    best, name = (0, 0), "none"
    for n, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        key = (ov, -(e - s))
        if n != TR.WINDOW and ov > 0 and key > best:
            best, name = key, n
    return name


def busiest_threads(gap: Tuple[int, int], threads: Sequence[list],
                    top: int = 3) -> List[Tuple[str, str, float]]:
    """The host events (of any thread) that overlap ``gap`` most, as
    ``(thread, event, seconds of overlap)``."""
    ov = []
    for thread, n, s, e in threads:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > 0 and n != TR.WINDOW:
            ov.append((thread, n, o / 1e9))
    return sorted(ov, key=lambda r: -r[2])[:top]


@dataclasses.dataclass
class Scoped:
    """Per-device means over the traced window, in seconds."""

    busy_s: float
    scope_s: Dict[str, float]
    op_s: Dict[str, float]
    host_spans: Dict[str, List[float]]
    idle_gaps: List[Tuple[str, float]]
    stalls: List[Tuple[float, str, list]]

    def breakdown(self, top: int = TR.TOP) -> List[list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return [[n, s] for n, s in ops]


def reduce(records: dict, scopes: Dict[str, Dict[str, str]],
           device_ids: Optional[Sequence[int]] = None) -> Scoped:
    """Device time per scope over :func:`bench.trace.load`'s records
    merged with :func:`load`'s, each op looked up in the executable
    whose ``XLA Modules`` event holds it; a scoped op is named
    ``<scope>/<op>``."""
    host = [tuple(h) for h in records["host"]]
    windows = [(s, e) for n, s, e in host if n == TR.WINDOW]
    devs = records["devices"]
    ids = [str(i) for i in device_ids] if device_ids is not None \
        else sorted(devs)
    ids = [i for i in ids if devs.get(i)]
    if not ids:
        raise ValueError("the trace holds no op ran on the cell's devices")
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(ev[1] for i in ids for ev in devs[i])
        hi = max(ev[2] for i in ids for ev in devs[i])
    busy = 0
    scope_ns: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, int] = defaultdict(int)
    all_gaps: list = []
    for i in ids:
        mods = sorted((s, e, n) for n, s, e in
                      records.get("modules", {}).get(i, []))
        starts = [s for s, _, _ in mods]
        evs = [(n, max(s, lo), min(e, hi), s) for n, s, e, _ in devs[i]
               if e > lo and s < hi]
        b = TR.union([(s, e) for _, s, e, _ in evs])
        busy += TR.length(b)
        for n, s, e, s0 in evs:
            k = bisect.bisect_right(starts, s0) - 1
            mod = mods[k][2] if k >= 0 and s0 < mods[k][1] else None
            scope = scopes.get(mod, {}).get(instruction(n)) if mod else None
            scope_ns[scope or UNSCOPED] += e - s
            op_ns[f"{scope}/{n}" if scope else n] += e - s
        all_gaps += TR.gaps(b, lo, hi)
    n = len(ids)
    spans: Dict[str, List[float]] = defaultdict(list)
    for name, s, e in host:
        if name.startswith(PROGRAM_SPANS) and s >= lo and e <= hi:
            spans[name].append((e - s) / 1e9)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])
    threads = records.get("threads", [])
    return Scoped(
        busy_s=busy / n / 1e9,
        scope_s={k: v / n / 1e9 for k, v in scope_ns.items()},
        op_s={k: v / n / 1e9 for k, v in op_ns.items()},
        host_spans=dict(spans),
        idle_gaps=[(label(g, host), (g[1] - g[0]) / 1e9)
                   for g in longest[:TR.TOP]],
        stalls=[((g[1] - g[0]) / 1e9, label(g, host),
                 busiest_threads(g, threads))
                for g in longest if g[1] - g[0] >= STALL_S * 1e9])
