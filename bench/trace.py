"""From the profiler's trace of a window to device busy and idle time.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain records, keeping only what the reduction needs: each TPU's op
events (plane ``/device:TPU:<id>``, line ``XLA Ops``) and the host's
annotation events (``bench.*`` from the benchmark, ``serve.*`` and the
other ``repro`` spans mirrored by ``$REPRO_TELEMETRY_JAX``).
:func:`reduce` turns them into a :class:`Summary`:

- the window: the ``bench.window`` host annotation (the measured window);
- busy: the union of op intervals inside the window, per device;
- kernels: the union of the Pallas kernels' intervals (Mosaic custom
  calls);
- op time per instruction (name, opcode, result shape);
- idle gaps: the complement of busy inside the window, each labelled
  with the host annotation that overlaps it most.

Times on several devices are averaged over the devices.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
WINDOW = "bench.window"
#: host annotations kept for labelling gaps
HOST_PREFIXES = ("bench.", "serve.", "execute.", "plan.", "tile.",
                 "pyramid.", "stream.")

Interval = Tuple[int, int]
#: entries of each list in a breakdown
TOP = 10


def op_label(text: str) -> str:
    """A short, stable label for the breakdown: the instruction's name,
    its opcode and its (first) result shape, without layouts."""
    m = re.match(r"%?(\S+) = \(?(\w+\[[^\]]*\])?.*?\s([\w-]+)\(", text)
    if not m:
        return text[:80]
    name, shape, opcode = m.groups()
    return f"{name} {opcode} {shape or ''}".strip()


def is_kernel(text: str) -> bool:
    """A Pallas kernel: a Mosaic custom call on the TPU."""
    return 'custom_call_target="tpu_custom_call"' in text


def load(trace_dir) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir`` as plain records:
    ``{"devices": {id: [[op_label, start_ns, end_ns, kernel], ...]},
    "host": [[name, start_ns, end_ns], ...]}``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices: Dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OP_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    [op_label(e.name), int(e.start_ns), int(e.end_ns),
                     is_kernel(e.name)]
                    for e in line.events)
            elif not m and plane.name.startswith("/host:"):
                host.extend([e.name, int(e.start_ns), int(e.end_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return {"devices": {str(k): v for k, v in devices.items()},
            "host": host}


def save(records: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(records, f)


def read_saved(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of merged ``busy`` inside ``[lo, hi)``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label(gap: Interval, host: Sequence[Tuple[str, int, int]]) -> str:
    """The host annotation (other than the window) that overlaps the
    gap most, or ``"none"``."""
    best, name = 0, "none"
    for n, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if n != WINDOW and ov > best:
            best, name = ov, n
    return name


@dataclasses.dataclass
class Summary:
    """Per-device means over the traced window, in seconds."""

    window_s: float
    busy_s: float
    kernel_s: float
    devices: int
    op_s: Dict[str, float]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def breakdown(self, top: int = TOP) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:top]]}


def reduce(records: dict, device_ids: Optional[Sequence[int]] = None
           ) -> Summary:
    """Busy, kernel and idle time over the window."""
    host = [tuple(h) for h in records["host"]]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    devs = records["devices"]
    ids = [str(i) for i in device_ids] if device_ids is not None \
        else sorted(devs)
    ids = [i for i in ids if devs.get(i)]
    if not ids:
        raise ValueError("the trace holds no op ran on the cell's "
                         "devices")
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(ev[1] for i in ids for ev in devs[i])
        hi = max(ev[2] for i in ids for ev in devs[i])
    busy = kern = 0
    op_ns: Dict[str, int] = defaultdict(int)
    all_gaps: List[Tuple[str, float]] = []
    for i in ids:
        evs = [(n, max(s, lo), min(e, hi), k) for n, s, e, k in devs[i]
               if e > lo and s < hi]
        b = union([(s, e) for _, s, e, _ in evs])
        busy += length(b)
        kern += length(union([(s, e) for _, s, e, k in evs if k]))
        for n, s, e, _ in evs:
            op_ns[n] += e - s
        all_gaps += gaps(b, lo, hi)
    n = len(ids)
    # only the longest gaps are reported, so only they are labelled
    all_gaps = [(label(g, host), (g[1] - g[0]) / 1e9) for g in
                sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]]
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
                   kernel_s=kern / n / 1e9,
                   devices=n,
                   op_s={k: v / n / 1e9 for k, v in op_ns.items()},
                   idle_gaps=all_gaps)
