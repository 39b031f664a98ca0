"""Device time put on the program's layers: the scoped reduction on
hand-made records, the innermost-annotation label, the readers of the
scope, span and compile metrics, and the module events of a trace
recorded on a TPU v5e (``data/``)."""
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run as R
from bench import scopes as SC
from bench import trace as TR
from repro import telemetry as T

DATA = Path(__file__).resolve().parent / "data"

FWD = {"fusion.1": "dwt.to_planes", "copy.2": "dwt.pad",
       "dwt_fwd_l0_s0.1": "dwt.level0"}
INV = {"fusion.1": "dwt.from_planes"}


def test_scope_seconds_puts_op_time_on_scopes():
    op_s = {"fusion.1 fusion f32[2211840,3]": 3.0,
            "copy.2 copy f32[3,1088,2048]": 0.5,
            "dwt_fwd_l0_s0.1 custom-call f32[3,1080,2048]": 0.25,
            "copy.9 copy f32[3]": 0.125}
    assert SC.scope_seconds(op_s, FWD) == {
        "dwt.to_planes": 3.0, "dwt.pad": 0.5, "dwt.level0": 0.25,
        "unscoped": 0.125}


def _records():
    # one device over a window 0..100 ns: a forward (0-40) and an
    # inverse (50-80) executable, whose instruction names collide; a
    # 20 ns stall (80-100) in which a runtime thread is busy
    return {
        "devices": {"0": [["fusion.1 fusion f32[8]", 0, 30, False],
                          ["copy.2 copy f32[8]", 30, 40, False],
                          ["fusion.1 fusion f32[8]", 50, 70, False],
                          ["copy.7 copy f32[8]", 70, 80, False]]},
        "modules": {"0": [["jit_dwt_forward", 0, 40],
                          ["jit_dwt_inverse", 50, 80]]},
        "host": [["bench.window", 0, 100], ["bench.decode", 45, 100],
                 ["execute.inverse", 80, 90], ["plan.compile", 90, 95]],
        "threads": [["main/1", "bench.decode", 45, 100],
                    ["tfrt-non-blocking-queue/3", "ThunkExecute", 78, 99]]}


def test_reduce_looks_each_op_up_in_its_executable(monkeypatch):
    monkeypatch.setattr(SC, "STALL_S", 15e-9)
    s = SC.reduce(_records(), {"jit_dwt_forward": FWD,
                               "jit_dwt_inverse": INV})
    assert s.busy_s == pytest.approx(70e-9)
    assert s.scope_s == pytest.approx({
        "dwt.to_planes": 30e-9, "dwt.pad": 10e-9,
        "dwt.from_planes": 20e-9, "unscoped": 10e-9})
    assert [n for n, _ in s.breakdown()] == [
        "dwt.to_planes/fusion.1 fusion f32[8]",
        "dwt.from_planes/fusion.1 fusion f32[8]",
        "dwt.pad/copy.2 copy f32[8]", "copy.7 copy f32[8]"]
    assert s.host_spans == pytest.approx({"execute.inverse": [10e-9],
                                          "plan.compile": [5e-9]})
    # the 20 ns gap: inside bench.decode (20) over execute.inverse (10)
    assert s.idle_gaps[0] == ("bench.decode", pytest.approx(20e-9))
    (gap, name, threads), = s.stalls
    assert gap == pytest.approx(20e-9) and name == "bench.decode"
    assert threads[0] == ("main/1", "bench.decode", pytest.approx(20e-9))
    assert threads[1][:2] == ("tfrt-non-blocking-queue/3", "ThunkExecute")


def test_reduce_without_a_map_leaves_every_op_unscoped():
    s = SC.reduce(_records(), {})
    assert s.scope_s == pytest.approx({"unscoped": 70e-9})


@pytest.mark.parametrize("gap,want", [
    ((20, 28), "bench.wait"), ((30, 60), "serve.stack_h2d"),
    ((80, 90), "none"), ((12, 18), "execute.inverse")])
def test_label_prefers_the_innermost_annotation_on_a_tie(gap, want):
    host = [("bench.window", 0, 100), ("bench.wait", 10, 30),
            ("serve.stack_h2d", 25, 60), ("bench.decode", 10, 20),
            ("execute.inverse", 11, 19)]
    assert SC.label(gap, host) == want
    if want != "execute.inverse":         # the plain rule agrees elsewhere
        assert TR.label(gap, host) == want


def _ctx(trace=None):
    return SimpleNamespace(trace=trace, setup_s=0.0,
                           t_start=time.perf_counter())


def _summary():
    return TR.Summary(window_s=1.0, busy_s=0.5, kernel_s=0.0, devices=1,
                      op_s={"fusion.1 fusion f32[8]": 0.4,
                            "copy.9 copy f32[8]": 0.1}, idle_gaps=[])


@pytest.mark.parametrize("metric", [
    "to_planes_share.encode", "from_planes_share.decode",
    "enqueue_ms.encode", "executor_compile_s"])
def test_readers_return_none_on_a_run_without_map_span_or_compile(metric):
    assert T.op_scopes() == {}
    assert R.load_metric(metric).read(_ctx(_summary())) is None
    assert R.load_metric(metric).read(_ctx()) is None


def test_scope_share_readers_read_the_recorded_map(monkeypatch):
    maps = {"jit_dwt_forward": {"fusion.1": "dwt.to_planes"},
            "jit_dwt_inverse": {"fusion.1": "dwt.from_planes",
                                "copy.9": "dwt.from_planes"}}
    monkeypatch.setattr(T, "op_scopes", lambda: maps)
    ctx = _ctx(_summary())
    assert R.load_metric("to_planes_share.encode").read(ctx) == \
        pytest.approx(80.0)
    assert R.load_metric("from_planes_share.decode").read(ctx) == \
        pytest.approx(100.0)


def test_enqueue_and_compile_readers_read_the_program():
    from repro.engine import executor as X
    ctx = _ctx()
    T.set_mode("spans")
    for d in (0.002, 0.004):
        T.TRACER.add(T.SpanRecord("execute.inverse", ctx.t_start + 1, d,
                                  1, None, {}, "main"))
    T.TRACER.add(T.SpanRecord("execute.forward", ctx.t_start - 1, 9.0,
                              2, None, {}, "main"))   # before the window
    X.COMPILE_SECONDS.inc(1.5, op="dwt_inverse")
    X.COMPILE_SECONDS.inc(0.5, op="dwt_inverse_l0")
    assert R.load_metric("enqueue_ms.decode").read(ctx) == \
        pytest.approx(3.0)
    assert R.load_metric("executor_compile_s").read(ctx) == \
        pytest.approx(2.0)


def test_load_reads_module_events_and_every_thread_of_a_tpu_xplane():
    rec = SC.load(DATA)
    mods = rec["modules"]["0"]
    assert {m for m, _, _ in mods} == {"jit_run"}
    assert all(s < e for _, s, e in mods)
    threads = {t for t, *_ in rec["threads"]}
    assert "tfrt-non-blocking-queue/346" in threads
    ops = TR.load(DATA)["devices"]["0"]
    inside = sum(any(s <= o[1] < e for _, s, e in mods) for o in ops)
    assert inside == len(ops)          # every op ran inside a module event
