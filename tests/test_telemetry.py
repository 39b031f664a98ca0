"""repro.telemetry: registry semantics, span tracing, exporters, the
engine.stats() schema contract, and the off-mode overhead guard.

The contract under test is PR 8's acceptance bar: every pre-existing
``engine.stats()`` key survives on top of the central registry, spans
nest correctly across the plan -> compile -> execute -> serve pipeline
and export as Perfetto-loadable Chrome-trace JSON, the Prometheus text
exposition round-trips, and ``REPRO_TELEMETRY=off`` turns every
instrument site into a no-op.
"""
import asyncio
import json

import numpy as np
import pytest

from repro import engine
from repro import telemetry as T
from repro.core import dwt2
from repro.telemetry.registry import MAX_SERIES, MetricsRegistry


# per-test isolation (mode, span ring, registry reset) now lives in
# tests/conftest.py::_isolated_planes

# -- registry ----------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help text")
    c.inc()
    c.inc(2, backend="jnp")
    assert c.value() == 1                # the unlabeled series is its own
    assert c.value(backend="jnp") == 2
    g = reg.gauge("g")
    g.set(1.5, op="fwd")
    assert g.value(op="fwd") == 1.5
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    (row,) = h.series()
    assert row["count"] == 3 and row["sum"] == pytest.approx(5.55)
    assert row["buckets"] == {0.1: 1, 1.0: 2}        # cumulative


def test_registry_kind_mismatch_rejected():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="counter"):
        reg.gauge("x")


def test_declared_labelnames_reject_typos():
    reg = MetricsRegistry()
    c = reg.counter("strict_total", labelnames=("backend", "fuse"))
    c.inc(backend="jnp", fuse="none")
    with pytest.raises(ValueError, match="declares labels"):
        c.inc(backend="jnp", fues="none")
    with pytest.raises(ValueError, match="declares labels"):
        c.inc(backend="jnp")


def test_series_cardinality_cap_drops_not_raises():
    reg = MetricsRegistry()
    c = reg.counter("wide_total")
    for i in range(MAX_SERIES + 10):
        c.inc(user=str(i))
    assert len(c.series()) == MAX_SERIES
    assert reg.dropped_series == 10
    # existing series still record after the cap is hit
    c.inc(user="0")
    assert c.value(user="0") == 2


def test_registry_reset_keeps_definitions():
    reg = MetricsRegistry()
    c = reg.counter("r_total", "kept help", labelnames=("k",))
    c.inc(k="a")
    reg.reset()
    assert c.value(k="a") == 0.0
    assert reg.get("r_total") is c and c.help == "kept help"
    c.inc(k="a")                       # definitions (labelnames) survive
    assert c.value(k="a") == 1


def test_counter_alias_is_read_write_mapping():
    reg = MetricsRegistry()
    alias = T.CounterAlias({"hits": ("alias_total", {"kind": "hit"}),
                            "misses": ("alias_total", {"kind": "miss"})},
                           registry=reg)
    reg.counter("alias_total").inc(3, kind="hit")
    assert alias["hits"] == 3 and alias["misses"] == 0
    assert isinstance(alias["hits"], int)
    assert dict(alias) == {"hits": 3, "misses": 0}
    assert sum(alias.values()) == 3
    alias.update(hits=0, misses=5)     # legacy reset/write idiom
    assert alias["hits"] == 0 and alias["misses"] == 5
    assert "hits" in alias and len(alias) == 2


# -- prometheus exposition --------------------------------------------

def test_prometheus_text_round_trip():
    reg = MetricsRegistry()
    reg.counter("rt_total", 'tricky "help"').inc(2, path='a"b', nl="x")
    reg.gauge("rt_gauge").set(1.25)
    h = reg.histogram("rt_seconds", buckets=(0.1, 1.0))
    h.observe(0.05, op="f")
    h.observe(3.0, op="f")
    text = T.prometheus_text(reg)
    assert "# TYPE rt_total counter" in text
    assert "# TYPE rt_seconds histogram" in text
    parsed = T.parse_prometheus_text(text)
    assert parsed["rt_total"] == [({"path": 'a"b', "nl": "x"}, 2.0)]
    assert parsed["rt_gauge"] == [({}, 1.25)]
    buckets = {lb["le"]: v for lb, v in parsed["rt_seconds_bucket"]}
    assert buckets == {"0.1": 1.0, "1": 1.0, "+Inf": 2.0}
    assert parsed["rt_seconds_count"] == [({"op": "f"}, 2.0)]
    assert parsed["rt_seconds_sum"][0][1] == pytest.approx(3.05)


def test_global_exposition_contains_engine_counters():
    dwt2(np.zeros((16, 16), np.float32), levels=1)
    text = T.prometheus_text()
    parsed = T.parse_prometheus_text(text)
    assert "repro_plan_executions_total" in parsed
    assert "repro_plan_cache_lookups_total" in parsed


# -- spans -------------------------------------------------------------

def test_spans_noop_outside_spans_mode():
    with T.span("quiet.op") as sp:
        pass
    assert sp is T.NOOP_SPAN and sp.duration is None
    assert T.TRACER.records() == []


def test_span_nesting_and_parenting():
    T.set_mode("spans")
    with T.span("outer", a=1):
        with T.span("inner"):
            assert T.current_span().name == "inner"
        with T.span("inner2"):
            pass
    recs = T.TRACER.records()
    by_name = {r.name: r for r in recs}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["inner2"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].parent_id is None
    assert by_name["outer"].labels == {"a": 1}
    # exit order: inner completes (and records) before outer
    assert recs.index(by_name["inner"]) < recs.index(by_name["outer"])
    assert by_name["outer"].dur_s >= by_name["inner"].dur_s


def test_span_ring_is_bounded_and_counts_drops():
    tracer = T.SpanTracer(capacity=4)
    for i in range(10):
        rec = T.SpanRecord(name=f"s{i}", start_s=float(i), dur_s=0.1,
                           span_id=i + 1, parent_id=None, labels={},
                           thread="t")
        tracer.add(rec)
    st = tracer.stats()
    assert st["resident"] == 4 and st["recorded"] == 10
    assert st["dropped"] == 6
    assert [r.name for r in tracer.records()] == ["s6", "s7", "s8", "s9"]


def test_span_summary_aggregates_by_name():
    T.set_mode("spans")
    for _ in range(3):
        with T.span("agg.op"):
            pass
    with T.span("agg.other"):
        pass
    rows = T.span_summary()
    by_name = {r["name"]: r for r in rows}
    assert by_name["agg.op"]["count"] == 3
    assert by_name["agg.op"]["total_s"] >= by_name["agg.op"]["max_s"]
    assert by_name["agg.op"]["mean_s"] == pytest.approx(
        by_name["agg.op"]["total_s"] / 3)


def test_chrome_trace_of_pyramid_dwt2_is_valid_and_nested(tmp_path):
    """Acceptance bar: the trace of a fused-pyramid dwt2 loads as
    Chrome-trace JSON with the pyramid launch nested under the
    execution span."""
    T.set_mode("spans")
    x = np.random.default_rng(0).standard_normal((64, 64)) \
        .astype(np.float32)
    dwt2(x, levels=2, fuse="pyramid", backend="pallas")
    path = T.write_chrome_trace(tmp_path / "trace.json")
    doc = json.loads(open(path).read())
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no complete events recorded"
    for e in xs:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert e["dur"] >= 0
    names = {e["name"] for e in xs}
    assert {"plan.build", "execute.forward", "pyramid.launch"} <= names
    by_id = {e["args"]["span_id"]: e for e in xs}
    launch = next(e for e in xs if e["name"] == "pyramid.launch")
    assert by_id[launch["args"]["parent_id"]]["name"] == "execute.forward"
    # thread metadata lanes exist for every tid used
    meta_tids = {e["tid"] for e in events if e["ph"] == "M"}
    assert {e["tid"] for e in xs} <= meta_tids


def test_serve_pipeline_emits_nested_spans():
    """Acceptance bar: a served batch produces the enqueue -> flush ->
    stack/h2d -> execute -> scatter span chain."""
    from repro.serve import ServeConfig, serve_map
    T.set_mode("spans")
    imgs = [np.random.default_rng(i).standard_normal((16, 16))
            .astype(np.float32) for i in range(3)]
    serve_map(imgs, config=ServeConfig(max_batch=2), levels=1)
    names = {r.name for r in T.TRACER.records()}
    assert {"serve.enqueue", "serve.bucket_flush", "serve.batch",
            "serve.stack_h2d", "serve.execute",
            "serve.scatter"} <= names
    by_id = {r.span_id: r for r in T.TRACER.records()}
    for r in T.TRACER.records():
        if r.name in ("serve.stack_h2d", "serve.execute",
                      "serve.scatter"):
            assert by_id[r.parent_id].name == "serve.batch"


# -- mode gating / overhead guard -------------------------------------

def test_off_mode_is_a_noop_everywhere():
    T.set_mode("off")
    T.reset()
    from repro.engine import plan as P
    k = dict(op="forward", backend="jnp", fuse="none",
             scheme="ns-polyconv")
    before = P.EXECUTIONS.value(**k)
    dwt2(np.zeros((16, 16), np.float32), levels=1)
    assert P.EXECUTIONS.value(**k) == before
    assert T.TRACER.records() == []
    assert T.roofline() == []
    # reads and exports still work under off
    assert isinstance(T.prometheus_text(), str)
    assert engine.stats()["telemetry"]["mode"] == "off"


def test_counters_mode_skips_spans_but_counts():
    from repro.engine import plan as P
    k = dict(op="forward", backend="jnp", fuse="none",
             scheme="ns-polyconv")
    before = P.EXECUTIONS.value(**k)
    dwt2(np.zeros((16, 16), np.float32), levels=1)
    assert P.EXECUTIONS.value(**k) == before + 1
    assert T.TRACER.records() == []


def test_mode_env_reload(monkeypatch):
    monkeypatch.setenv(T.MODE_ENV, "spans")
    T.reload()
    assert T.mode() == "spans" and T.CONFIG.spans_on
    monkeypatch.setenv(T.MODE_ENV, "bogus")
    with pytest.raises(ValueError, match="bogus"):
        T.reload()
    monkeypatch.delenv(T.MODE_ENV)
    T.reload()
    assert T.mode() == T.DEFAULT_MODE


# -- attribution -------------------------------------------------------

def test_attribution_publishes_roofline_gauges():
    plan = engine.get_plan(shape=(16, 16), levels=1, backend="jnp",
                           fuse="levels", cache=engine.PlanCache())
    row = T.record_execution(plan, 0.5, op="forward")
    assert row is not None
    assert row["gbps"] == pytest.approx(row["hbm_bytes"] / 0.5 / 1e9)
    assert row["macs_per_s"] == pytest.approx(row["macs"] / 0.5)
    rows = [r for r in T.roofline()
            if r["op"] == "forward" and r["backend"] == "jnp"
            and r["seconds"] == 0.5]
    assert rows and rows[0]["gbps"] == pytest.approx(row["gbps"])
    # inputs are cached on the plan: second call reuses them
    assert plan._attr_inputs["hbm_bytes"] == row["hbm_bytes"]
    assert T.record_execution(plan, 0.25, op="forward")["gbps"] == \
        pytest.approx(2 * row["gbps"])


def test_attribution_handles_tap_opt_off_and_bad_measurements():
    plan = engine.get_plan(shape=(16, 16), levels=1, backend="jnp",
                           fuse="none", tap_opt="off",
                           cache=engine.PlanCache())
    row = T.record_execution(plan, 0.1, op="forward")
    assert row is not None and row["macs"] is None   # no compiled MACs
    assert T.record_execution(plan, 0.0) is None     # unusable timing
    assert T.record_execution(plan, -1.0) is None


def test_execute_spans_no_longer_feed_the_roofline_gauges():
    """An ``execute.*`` span times the enqueue; only profile_plan's
    blocked timing publishes achieved GB/s."""
    T.set_mode("spans")
    dwt2(np.zeros((16, 16), np.float32), levels=1, backend="jnp",
         fuse="levels")
    assert "execute.forward" in {r.name for r in T.TRACER.records()}
    assert T.roofline() == []


# -- op scopes and executor compiles -----------------------------------

def _compile_pair(shape=(3, 32, 64), levels=2, backend="pallas"):
    """Forward and inverse of a fresh plan (no shared plan cache, so
    its executors compile here)."""
    plan = engine.get_plan(shape=shape, levels=levels, backend=backend,
                           fuse="levels", cache=engine.PlanCache())
    x = np.random.default_rng(0).standard_normal(shape).astype(np.float32)
    out = plan.execute_inverse(plan.execute(x))
    np.testing.assert_allclose(np.asarray(out), x, atol=1e-4)
    return plan


def test_op_scopes_name_the_layers_of_a_compiled_plan():
    T.set_mode("spans")
    _compile_pair(shape=(3, 32, 512))      # wide enough for the split kernel
    scopes = T.op_scopes()
    assert {"jit_dwt_forward", "jit_dwt_inverse"} <= set(scopes)
    fwd = set(scopes["jit_dwt_forward"].values())
    inv = set(scopes["jit_dwt_inverse"].values())
    assert {"dwt.to_planes", "dwt.pad", "dwt.level0", "dwt.level1"} <= fwd
    assert {"dwt.from_planes", "dwt.pad"} <= inv
    assert "dwt.from_planes" not in fwd and "dwt.to_planes" not in inv
    names = [r.name for r in T.TRACER.records()]
    assert names.count("plan.compile") == 2
    assert T.snapshot()["repro_executor_compiles_total"]["series"] == [
        {"labels": {"op": "dwt_forward"}, "value": 1},
        {"labels": {"op": "dwt_inverse"}, "value": 1}]


def test_counters_mode_keeps_no_scope_map_but_counts_compiles():
    from repro.engine import executor as X
    _compile_pair(shape=(2, 32, 32), levels=1, backend="jnp")
    assert T.op_scopes() == {}
    assert T.TRACER.records() == []
    assert X.COMPILES.value(op="dwt_forward") == 1
    assert X.COMPILES.value(op="dwt_inverse") == 1
    assert X.COMPILE_SECONDS.value(op="dwt_forward") > 0


def test_compile_counters_count_compiles_not_calls():
    from repro.engine import executor as X
    plan = _compile_pair(shape=(2, 32, 32), levels=1, backend="jnp")
    x = np.ones((2, 32, 32), np.float32)
    for _ in range(3):
        plan.execute(x)
    assert X.COMPILES.value(op="dwt_forward") == 1


def test_kernels_and_per_level_executables_have_stable_names():
    from repro.engine import executor as X
    from repro.kernels import polyphase as PP
    assert PP.kernel_name(False, 2, 0) == "dwt_fwd_l2_s0"
    assert PP.kernel_name(True, 0, 3) == "dwt_inv_l0_s3"
    T.set_mode("spans")
    plan = engine.get_plan(shape=(32, 32), levels=2, backend="pallas",
                           fuse="none", cache=engine.PlanCache())
    plan.execute(np.ones((32, 32), np.float32))
    assert {"jit_dwt_forward_l0", "jit_dwt_forward_l1"} <= set(
        T.op_scopes())
    assert X.COMPILES.value(op="dwt_forward_l1") == 1


HLO = """HloModule jit_dwt_forward, is_scheduled=true

%fused_computation (param_0: f32[4,8]) -> f32[2,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  %gather.1 = f32[2,8]{1,0} gather(f32[4,8]{1,0} %param_0), metadata={op_name="jit(dwt_forward)/dwt.level0/dwt.to_planes/gather"}
  %slice.2 = f32[2,8]{1,0} slice(%gather.1), metadata={op_name="jit(dwt_forward)/dwt.level0/dwt.to_planes/slice"}
  ROOT %concatenate.3 = f32[2,8]{1,0} concatenate(%slice.2), metadata={op_name="jit(dwt_forward)/dwt.level0/dwt.pad/concatenate"}
}

ENTRY %main.9 (x.1: f32[4,8]) -> f32[2,8] {
  %x.1 = f32[4,8]{1,0} parameter(0), metadata={op_name="x"}
  %copy.4 = f32[4,8]{0,1:T(8,128)} copy(%x.1), metadata={op_name="x"}
  %fusion.5 = f32[2,8]{1,0} fusion(%copy.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(dwt_forward)/dwt.level0/dwt.pad/concatenate"}
  %copy.6 = f32[2,8]{0,1} copy(f32[2,8]{1,0:T(8,128)} %fusion.5)
  %dwt_fwd_l0_s0.1 = f32[2,8]{1,0} custom-call(%copy.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(dwt_forward)/dwt.level0/dwt_fwd_l0_s0"}
  ROOT %bitcast.7 = f32[2,8]{1,0} bitcast(%dwt_fwd_l0_s0.1)
}
"""


def test_scope_map_rules_on_hlo_text():
    from repro.telemetry import scopes as SC
    assert SC.scope_of("jit(f)/dwt.level1/dwt.pad/jit(_pad)/slice") == \
        "dwt.pad"
    assert SC.scope_of("x") is None
    module, m = SC.parse(HLO)
    assert module == "jit_dwt_forward"
    assert m == {
        # the fusion: two of its three instructions are dwt.to_planes
        "fusion.5": "dwt.to_planes",
        # a copy of the parameter takes its user's scope (and so does
        # the parameter, which runs no device op)
        "copy.4": "dwt.to_planes",
        "x.1": "dwt.to_planes",
        # a layout copy and a bitcast take their producer's
        "copy.6": "dwt.to_planes",
        "dwt_fwd_l0_s0.1": "dwt.level0",
        "bitcast.7": "dwt.level0"}
    assert T.record_op_scopes(HLO) == "jit_dwt_forward"
    assert T.op_scopes()["jit_dwt_forward"] == m
    T.reset()
    assert T.op_scopes() == {}


# -- engine.stats() schema contract -----------------------------------

def test_engine_stats_schema_exact_top_level_keys():
    s = engine.stats()
    assert sorted(s) == ["auto", "backends", "block_table", "faults",
                         "plan_cache", "plans", "pyramid", "serve",
                         "telemetry"]
    assert sorted(s["pyramid"]) == ["pyramid_kernel_launches",
                                    "vmem_fallbacks"]
    assert sorted(s["auto"]) == ["choices", "cold_fallbacks",
                                 "predictions", "store_hits"]
    assert {"submitted", "served", "failed", "rejected", "batches",
            "p50_ms", "p99_ms", "img_per_s", "mean_occupancy",
            "latency_samples", "latency_dropped", "deadline_exceeded",
            "quarantined", "breaker_rejections"} <= set(s["serve"])
    assert {"active", "enabled", "injections", "fallbacks",
            "retries"} <= set(s["faults"])
    assert sorted(s["telemetry"]) == ["dropped_series", "metrics",
                                      "mode", "series", "spans"]
    assert {"hits", "misses", "size", "maxsize"} <= set(s["plan_cache"])


def test_engine_stats_sections_degrade_to_zero_schema(monkeypatch):
    """A subsystem failing at read time must not change the stats()
    shape — its section degrades to the zeroed schema."""
    from repro.engine import cache as EC

    def boom():
        raise RuntimeError("serve backend unavailable")
    monkeypatch.setattr("repro.serve.metrics.serve_stats", boom)
    monkeypatch.setattr("repro.profiler.auto.auto_stats", boom)
    monkeypatch.setattr("repro.faults.stats", boom)
    s = engine.stats()
    assert s["serve"] == EC._SERVE_ZERO
    assert s["auto"] == EC._AUTO_ZERO
    assert s["faults"] == EC._FAULTS_ZERO
    assert sorted(s) == ["auto", "backends", "block_table", "faults",
                         "plan_cache", "plans", "pyramid", "serve",
                         "telemetry"]


def test_serve_latency_window_bounded_and_drops_counted(monkeypatch):
    import repro.serve.metrics as SM
    monkeypatch.setattr(SM, "LATENCY_WINDOW", 8)
    m = SM.ServeMetrics()
    m.batch_done(real=6, padded=6, latencies_s=[0.01] * 6)
    s = m.snapshot()
    assert s["latency_samples"] == 6 and s["latency_dropped"] == 0
    m.batch_done(real=6, padded=6, latencies_s=[0.02] * 6)
    s = m.snapshot()
    assert s["latency_samples"] == 8
    assert s["latency_dropped"] == 4
    assert s["served"] == 12           # totals unaffected by the window
    assert s["p50_ms"] is not None


def test_legacy_counter_aliases_still_readable():
    from repro.engine import autotune as AT
    from repro.engine import plan as P
    from repro.profiler import auto as PA
    assert set(P.COUNTERS) == {"pyramid_kernel_launches",
                               "vmem_fallbacks"}
    assert set(AT.COUNTERS) == {"device_fallbacks"}
    assert set(PA.AUTO_COUNTERS) == {"predictions", "store_hits",
                                     "cold_fallbacks"}
    for alias in (P.COUNTERS, AT.COUNTERS, PA.AUTO_COUNTERS):
        for k, v in alias.items():
            assert isinstance(v, int) and v >= 0
