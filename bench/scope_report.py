#!/usr/bin/env python3
"""One traced run of a cell, with its device time put on program layers.

    python3 bench/scope_report.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints the same
result line, then a second JSON line read from the same trace with
:mod:`bench.scopes`: device seconds per ``dwt.*`` scope and
``"unscoped"``, the top ops under their scopes, the program's
``execute.*``/``plan.*`` spans in the window, the idle gaps labelled by
the innermost annotation, and each gap over 10 ms with the host events
(of any thread) that overlap it most.
"""
from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402
from bench import scopes as SC  # noqa: E402
from bench import trace as TR  # noqa: E402


def report(records: dict, scopes: dict) -> dict:
    s = SC.reduce(records, scopes)
    return {
        "busy_s": s.busy_s,
        "scope_share": {k: 100.0 * v / s.busy_s
                        for k, v in sorted(s.scope_s.items(),
                                           key=lambda kv: -kv[1])},
        "device_ops": s.breakdown(),
        "host_spans": {k: {"count": len(v), "mean_ms": 1e3 * statistics.fmean(v),
                           "max_ms": 1e3 * max(v)}
                       for k, v in s.host_spans.items()},
        "idle_gaps": [[n, g] for n, g in s.idle_gaps],
        "stalls": [[g, n, threads] for g, n, threads in s.stalls],
        "modules": sorted(scopes)}


def main(argv=None) -> int:
    args = R.parse_args(argv)
    args.trace = 1
    kept = {}
    plain = TR.load

    def load(trace_dir):
        # the run reads the trace once and then removes it: keep what
        # the scoped reduction needs on the way
        rec = plain(trace_dir)
        kept["records"] = dict(rec, **SC.load(trace_dir))
        return rec

    TR.load = load
    try:
        result = R.run(args)
    except R.BenchError as e:
        R.log(f"bench: {e}")
        return 1
    finally:
        TR.load = plain
    print(json.dumps(result), flush=True)
    from repro import telemetry
    print(json.dumps(report(kept["records"], telemetry.op_scopes())),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
