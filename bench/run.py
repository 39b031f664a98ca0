#!/usr/bin/env python3
"""Chip benchmark of the DWT engine: one cell, one process, one result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The mix names the loop that drives
it (``bench/drivers/<driver>.py``); each per-layer metric of
``BENCHMARK.json`` is read by ``bench/metrics/<metric>.py`` (the part of
the name before the first dot: ``idle_share.encode`` is read by
``idle_share.py``).  Everything is found by name, so a new
configuration, mix, loop or metric is a new file.

A run, in order: refuse any device but a TPU (no fallback to the CPU);
point JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
isolate the block table and profile store under
``.bench_out/<cell>/``; make the data on the device from
``--seed``; warm up the cell's own shapes (all of that is ``setup_s``);
measure for ``--seconds``; with ``--trace 1`` trace that window and
read the per-layer metrics from it; then check the outputs against the
float64 reference (``bench/reference.py``).  Progress goes to standard
error, the checks last; the last line of standard output is the JSON
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
MANIFEST = ROOT / "BENCHMARK.json"
CACHE_DIR = ROOT / ".jax_cache"
OUT = ROOT / ".bench_out"


sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import ideal_bytes  # noqa: E402
from bench.harness import (BenchError, Check, CompileStats, Ctx,  # noqa: E402
                           log)


# -- discovery by name ------------------------------------------------------

def _json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (BENCH / kind).glob("*.json"))
        raise BenchError(f"no {kind[:-1]} named {name!r} "
                         f"({path.relative_to(ROOT)}); known: {known}")
    return json.loads(path.read_text())


def load_config(name: str) -> dict:
    return _json("configs", name)


def load_traffic(name: str) -> dict:
    return _json("traffic", name)


def _module(kind: str, name: str):
    if not (BENCH / kind / f"{name}.py").is_file():
        known = sorted(p.stem for p in (BENCH / kind).glob("*.py")
                       if p.stem != "__init__")
        raise BenchError(f"no {kind[:-1]} named {name!r}; known: {known}")
    return importlib.import_module(f"bench.{kind}.{name}")


def load_driver(name: str):
    return _module("drivers", name)


def load_metric(name: str):
    """The reader of a per-layer metric: ``a.b`` is read by ``a.py``."""
    return _module("metrics", name.split(".", 1)[0])


def manifest() -> dict:
    return json.loads(MANIFEST.read_text())


def find_cell(name: str, bench: Optional[dict] = None) -> dict:
    bench = manifest() if bench is None else bench
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload named {name!r} in {MANIFEST.name}; "
                     f"known: {[c['name'] for c in bench['workloads']]}")


def cell_metrics(cell: dict, bench: dict, traced: bool) -> List[dict]:
    """The metrics this cell reports: its end-to-end ones untraced, its
    per-layer ones traced (a metric without ``workloads`` goes to every
    cell)."""
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


# -- the run -----------------------------------------------------------------

def _prepare_environment(cell: str, traced: bool) -> None:
    """Everything that has to be set before JAX and ``repro`` load."""
    CACHE_DIR.mkdir(exist_ok=True)      # JAX writes into it, never makes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # spans (mirrored into the profiler's trace) only in a traced run
    os.environ["REPRO_TELEMETRY"] = "spans" if traced else "counters"
    os.environ["REPRO_TELEMETRY_JAX"] = "1" if traced else "0"
    out = OUT / cell
    out.mkdir(parents=True, exist_ok=True)
    # static block rule and an empty profile store: no untracked file
    # on disk decides what runs
    for env, name in (("REPRO_BLOCK_TABLE", "BLOCK_TABLE.json"),
                      ("REPRO_PROFILE_STORE", "PROFILE_STORE.jsonl")):
        (out / name).unlink(missing_ok=True)
        os.environ[env] = str(out / name)


def _devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found: the first JAX device is "
                         f"{devs[0].platform!r}; this benchmark never runs "
                         f"on another platform")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_per_layer(ctx: Ctx, metrics: List[dict]) -> dict:
    """Each per-layer metric from its reader; a reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> dict:
    """One run of one cell; returns the result line's object."""
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"the system under test (src/repro) is not in "
                         f"{ROOT}")
    bench = manifest()
    cell = find_cell(args.workload, bench)
    traced = bool(args.trace)
    _prepare_environment(cell["name"], traced)
    config = load_config(cell["config"])
    traffic = load_traffic(cell["traffic"])
    driver = load_driver(traffic["driver"])
    metrics = cell_metrics(cell, bench, traced)
    import jax
    devices = _devices(cell["chips"])
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    if Path(cache) != CACHE_DIR:
        raise BenchError(f"compile cache at {cache}, not {CACHE_DIR}")
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} cache={cache}")
    ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=float(args.seconds), traced=traced,
              devices=devices, compile=CompileStats(),
              control=args.control, t_start=T_START)
    state = driver.setup(ctx)
    ctx.window = win = driver.run(ctx, state)
    log(f"[window] {win.seconds:.3f} s, {win.attempted} attempted, "
        f"{win.failed} failed; {ctx.compile.window_calls} backend compiles "
        f"in the window ({ctx.compile.window_s:.3f} s)")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices)}
    if device["memory_peak_bytes"] is not None:
        hbm = ideal_bytes.peaks(dev.device_kind)["hbm_bytes"]
        log(f"[memory] peak {device['memory_peak_bytes']} B on the fullest "
            f"chip, {100 * device['memory_peak_bytes'] / hbm:.1f} % of "
            f"its {hbm:.3g} B")
    result = {"correct": False, "attempted": win.attempted,
              "failed": win.failed}
    if traced:
        from bench import trace as TR
        ctx.trace = TR.reduce(TR.load(ctx.trace_dir),
                              [d.id for d in devices])
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["metrics"] = read_per_layer(ctx, metrics)
        result["breakdown"] = ctx.trace.breakdown()
    else:
        names = {m["name"]: m["unit"] for m in metrics}
        result["metrics"] = {k: {"value": v, "unit": names[k]}
                             for k, v in win.metrics.items() if k in names}
        result["metrics"]["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
        missing = set(names) - set(result["metrics"])
        if missing:
            raise BenchError(f"the {traffic['driver']} driver reported no "
                             f"{sorted(missing)}")
    result["device"] = device
    checks = driver.check(ctx, state)
    checks.append(Check("window_compiles", ctx.compile.window_calls, 0))
    checks.append(Check("failed_requests", win.failed, 0))
    result["correct"] = all(c.ok for c in checks)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"[check] {c.name} = {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="compute in bfloat16: the control that the "
                         "output check has to refuse (never a benchmark "
                         "run)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run(args)
    except BenchError as e:
        log(f"bench: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
