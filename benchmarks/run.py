"""Benchmark orchestrator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH] \
        [--backends jnp,pallas,xla] [--trace PATH]

Sections:
  1. table1   — paper Table 1 (steps + operation counts), exact-match vs
                the paper's OpenCL column, plus the tap-program
                compiler's lowered/compiled MAC counts.
  2. fig789   — paper Figures 7/8/9 (throughput vs image size per scheme):
                CPU-measured.
  3. engine   — plan/executor engine: batched images/sec, plan-cached vs
                seed-style per-call dispatch (both backends).
  4. kernels  — per-kernel roofline (steps -> HBM round trips on TPU)
                + per-plan launch summary.
  5. auto     — profile-guided selection: warm the trace store on a
                small grid, assert ``backend="auto"`` picks within 10%
                of the best manual (backend, fuse) per cell, report
                cost-model prediction error (a BENCH_10 CI gate).
  6. serve    — serving runtime: batched DwtServer vs per-request
                dispatch at concurrency 16; gates speedup >= 2x and
                bit-identical coefficients (a BENCH_10 CI gate).
  7. compress — DWT gradient compression (framework integration).
  8. roofline — per-(arch x shape x mesh) summary from the dry-run
                artifacts (if present).

``--json PATH`` additionally writes every section's rows as a single
machine-readable document (throughput numbers, op counts, and the
op-count regression verdict), plus run metadata (device kind, platform,
jax/jaxlib versions, interpret-mode flag) so artifacts and profiler
traces are attributable across machines, for CI trend tracking.  The
document embeds a ``telemetry`` section: the full metrics-registry
snapshot accumulated over the run plus the top-spans table
(``repro.telemetry.span_summary``) when span tracing was on.
``benchmarks/compare_bench.py`` diffs two such documents and gates
throughput regressions against the committed baseline
(``BENCH_10.json``):

    PYTHONPATH=src python -m benchmarks.run --quick --json BENCH_10.json

``--trace PATH`` forces ``REPRO_TELEMETRY=spans`` for the run and
writes the Chrome-trace JSON of the span ring to PATH — load it at
https://ui.perfetto.dev (CI uploads this as an artifact).

``--backends`` limits the *measured* backends to a comma-separated
subset of the registered ones (the analytic sections are
backend-independent and always run); e.g. ``--backends xla`` is the CI
smoke for the grouped-conv executor.
"""
import json
import sys
import time


def _flag_value(name):
    if name not in sys.argv:
        return None
    i = sys.argv.index(name)
    if i + 1 >= len(sys.argv):
        raise SystemExit(f"{name} requires an argument")
    return sys.argv[i + 1]


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    quick = "--quick" in sys.argv
    json_path = _flag_value("--json")
    trace_path = _flag_value("--trace")
    from repro import telemetry as T
    if trace_path:
        T.set_mode("spans")     # the trace needs the span ring populated
    from repro import engine
    backends = _flag_value("--backends")
    backends = (engine.available_backends() if backends is None
                else tuple(backends.split(",")))
    unknown = set(backends) - set(engine.available_backends())
    if unknown:
        raise SystemExit(f"unknown backends {sorted(unknown)}; registered: "
                         f"{engine.available_backends()}")
    t0 = time.time()
    from repro.profiler import runtime_meta
    doc = {"quick": quick, "backends": list(backends),
           "meta": {**runtime_meta(), "argv": sys.argv[1:],
                    "timestamp": time.time()}}
    print(f"# run meta: {doc['meta']}")

    from benchmarks import table1_ops
    print("=" * 72)
    matched, total, regressions, t1_rows = table1_ops.main()
    assert matched >= 13, f"Table 1 regression: {matched}/{total}"
    assert regressions == 0, \
        f"op-count regression: {regressions} schemes compiled WORSE"
    doc["table1"] = {"rows": t1_rows, "paper_cells_matched": matched,
                     "paper_cells_total": total,
                     "compiler_op_regressions": regressions}

    print("=" * 72)
    from benchmarks import throughput
    doc["fig789"] = throughput.main(
        sizes=(512, 1024) if quick else (512, 1024, 2048))

    print("=" * 72)
    doc["engine"] = throughput.engine_throughput(
        batch_sizes=(1, 8) if quick else (1, 8, 32),
        reps=3 if quick else 5, backends=backends)

    print("=" * 72)
    doc["tiling"] = throughput.tiled_throughput(
        n=256 if quick else 512, tile=64 if quick else 128)

    print("=" * 72)
    doc["packets"] = throughput.packet_throughput(
        n=64 if quick else 128, reps=3 if quick else 5)

    print("=" * 72)
    doc["dwt3"] = throughput.dwt3_throughput(
        n=32 if quick else 64, t_frames=4 if quick else 8,
        reps=3 if quick else 5,
        backends=tuple(b for b in ("jnp", "xla") if b in backends))

    if "pallas" in backends:
        print("=" * 72)
        doc["pyramid"] = throughput.pyramid_throughput(
            n=32 if quick else 64, batch=2 if quick else 4)

    print("=" * 72)
    from benchmarks import kernel_bench
    doc["kernels"] = kernel_bench.main()
    # CI gate: the fused-pyramid megakernel must move strictly fewer
    # modelled HBM bytes than per-level kernels for every scheme
    worse = [r["scheme"] for r in doc["kernels"]["fuse_modes"]
             if not r["pyramid_bytes"] < r["levels_bytes"]]
    assert not worse, \
        f"fuse='pyramid' HBM bytes not below fuse='levels' for: {worse}"

    print("=" * 72)
    from benchmarks import profiler_bench
    doc["auto"] = profiler_bench.auto_bench(quick=quick)
    # CI gate: with a store warmed on the grid, the auto-picked config
    # must never be >10% slower than the best manual (backend, fuse)
    # for that cell, and auto output must be bit-identical to the
    # chosen backend's
    bad = [c for c in doc["auto"]["cells"]
           if c["auto_vs_best"] is None or c["auto_vs_best"] > 1.10]
    assert not bad, f"auto pick >10% worse than best manual config: {bad}"
    assert doc["auto"]["parity_bit_identical"], \
        "backend='auto' output != chosen backend output"

    print("=" * 72)
    from benchmarks import serve_bench
    doc["serve"] = serve_bench.serve_bench(quick=quick)
    # CI gates: the batched server must at least double per-request
    # throughput at concurrency 16, serving bitwise-identical results
    assert doc["serve"]["parity_bit_identical"], \
        "served coefficients != direct dwt2 coefficients"
    assert doc["serve"]["speedup"] >= serve_bench.SPEEDUP_GATE, \
        (f"batched serving speedup {doc['serve']['speedup']:.2f}x below "
         f"the {serve_bench.SPEEDUP_GATE}x gate")

    print("=" * 72)
    from benchmarks import compression_bench
    compression_bench.main()

    print("=" * 72)
    try:
        from benchmarks import roofline
        roofline.main()
    except Exception as e:  # artifacts may not exist yet
        print(f"# roofline artifacts not available: {e}")

    print("=" * 72)
    from repro import engine
    stats = engine.stats()
    doc["engine_stats"] = stats
    cache = stats["plan_cache"]
    pyr = stats["pyramid"]
    print(f"# engine stats: plan cache {cache['hits']} hits / "
          f"{cache['misses']} misses, {cache['size']} plans resident")
    print(f"# pyramid: {pyr['pyramid_kernel_launches']} megakernel "
          f"launches, {pyr['vmem_fallbacks']} VMEM fallbacks")
    auto = stats["auto"]
    print(f"# auto: {auto['predictions']} model predictions, "
          f"{auto['store_hits']} store hits, "
          f"{auto['cold_fallbacks']} cold-start fallbacks, "
          f"choices {auto['choices']}")
    print(f"# block table: "
          f"{stats['block_table']['device_fallbacks']} device-mismatch "
          f"fallbacks")
    srv = stats["serve"]
    if srv["served"]:
        print(f"# serve: {srv['served']} requests / {srv['batches']} "
              f"batches, occupancy {srv['mean_occupancy']:.2f}, "
              f"p50 {srv['p50_ms']:.2f} ms, p99 {srv['p99_ms']:.2f} ms")
    for row in stats["plans"]:
        tiling = (f" tiles={row['tile_grid']}x{row['tiles']} "
                  f"margin={row['halo_margin']}" if "tiles" in row else "")
        macs = (f" macs={row['compiled_macs']}" if "compiled_macs" in row
                else "")
        pyrw = (f" window={row['pyramid_window']}"
                if "pyramid_window" in row else "")
        fb = " FALLBACK" if "fallback" in row else ""
        print(f"#   {row['wavelet']}/{row['scheme']} L{row['levels']} "
              f"{row['shape']} {row['backend']}/{row['fuse']}"
              f"/{row['tap_opt']} steps={row['num_steps']}"
              f" launches={row['pallas_calls']}{macs}{tiling}{pyrw}{fb}")

    print("=" * 72)
    # telemetry accumulated over the whole run: registry snapshot always,
    # top-spans table when span tracing was on (--trace / REPRO_TELEMETRY)
    top_spans = T.span_summary(top=15)
    doc["telemetry"] = {"mode": T.mode(), "metrics": T.snapshot(),
                        "top_spans": top_spans}
    if top_spans:
        print("# top spans (by total time):")
        print("# name,count,total_s,mean_s,max_s")
        for r in top_spans:
            print(f"#   {r['name']},{r['count']},{r['total_s']:.4f},"
                  f"{r['mean_s']:.6f},{r['max_s']:.6f}")
    if trace_path:
        T.write_chrome_trace(trace_path)
        print(f"# wrote Perfetto/Chrome trace to {trace_path} "
              f"(load at https://ui.perfetto.dev)")

    print("=" * 72)
    doc["elapsed_s"] = time.time() - t0
    print(f"# benchmarks completed in {doc['elapsed_s']:.1f}s")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        print(f"# wrote machine-readable results to {json_path}")


if __name__ == "__main__":
    main()
