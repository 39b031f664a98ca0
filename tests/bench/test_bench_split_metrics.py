"""The readers of the split's metrics: ``split_fallbacks`` from the
program's counter, ``planes_roofline`` from a stand-in trace, with the
bytes counted by hand."""
from types import SimpleNamespace

import pytest

from bench import run as R
from bench import trace as TR
from repro import telemetry as T

SPLIT = R.load_metric("planes_roofline.s2")
FALLBACKS = R.load_metric("split_fallbacks.s2")
V5E = "TPU v5 lite"                  # 819e9 B/s in bench/peaks.json


def _ctx(op_s, shape=(10980, 10980), levels=2, done=4,
         io=("uint16", "float32")):
    trace = TR.Summary(window_s=1.0, busy_s=sum(op_s.values()) or 1.0,
                       kernel_s=0.0, devices=1, op_s=op_s, idle_gaps=[])
    return SimpleNamespace(
        trace=trace, config={"shape": list(shape), "levels": levels},
        devices=[SimpleNamespace(device_kind=V5E)],
        window=SimpleNamespace(extra={"transforms": done,
                                      "io_dtypes": io}))


def test_split_bytes_by_hand():
    # a band: 10980² read at 2 B and written at 4 B, then the 5490² LL
    # read and written at 4 B
    assert SPLIT.split_bytes((10980, 10980), 2, 2, 4) == \
        10980 * 10980 * 6 + 5490 * 5490 * 8 == 964_483_200
    # a DCI frame, 4 levels of float32: 8 B a pixel at each level
    px = [3 * (2160 >> l) * (4096 >> l) for l in range(4)]
    assert SPLIT.split_bytes((3, 2160, 4096), 4, 4, 4) == 8 * sum(px)


def test_planes_roofline_reads_split_and_shift_time(monkeypatch):
    maps = {"jit_dwt_forward": {"dwt_split.2": "dwt.to_planes",
                                "fusion.3": "dwt.level_shift",
                                "dwt_fwd_l0_s0.1": "dwt.level0"}}
    monkeypatch.setattr(T, "op_scopes", lambda: maps)
    # 4 transforms; split 4.0 ms and shift 0.8 ms of device time in all:
    # 1.2 ms a transform against the least 964483200 B / 819e9 B/s
    ctx = _ctx({"dwt_split.2 custom-call f32[1,5490,5490]": 4.0e-3,
                "fusion.3 fusion f32[10980,10980]": 0.8e-3,
                "dwt_fwd_l0_s0.1 custom-call f32[1,5490,5490]": 30e-3})
    least = 964_483_200 / 819e9
    assert SPLIT.read(ctx) == pytest.approx(100 * least / 1.2e-3)
    assert 98.13 < SPLIT.read(ctx) < 98.14


@pytest.mark.parametrize("maps,trace", [
    ({}, True), ({"jit_dwt_forward": {"dwt_fwd_l0_s0.1": "dwt.level0"}},
                 True),
    ({"jit_dwt_forward": {"dwt_split.2": "dwt.to_planes"}}, False)],
    ids=("no-map", "no-split-op", "untraced"))
def test_planes_roofline_is_none_without_a_split_to_read(maps, trace,
                                                        monkeypatch):
    monkeypatch.setattr(T, "op_scopes", lambda: maps)
    ctx = _ctx({"dwt_fwd_l0_s0.1 custom-call f32[8]": 1e-3})
    if not trace:
        ctx.trace = None
    assert SPLIT.read(ctx) is None


def test_split_fallbacks_counts_levels_that_took_slices():
    from repro.kernels import polyphase as PP
    assert FALLBACKS.read(None) is None          # nothing traced yet
    PP.SPLIT_PATHS.inc(path="kernel", level=0)
    PP.SPLIT_PATHS.inc(path="kernel", level=1)
    assert FALLBACKS.read(None) == 0
    PP.SPLIT_PATHS.inc(path="slices", level=2)
    PP.SPLIT_PATHS.inc(path="slices", level=2)   # traced twice
    PP.SPLIT_PATHS.inc(path="slices", level=3)
    assert FALLBACKS.read(None) == 2


def test_split_fallbacks_is_none_for_a_program_without_the_counter(
        monkeypatch):
    monkeypatch.setattr(T.REGISTRY, "get", lambda name: None)
    assert FALLBACKS.read(None) is None
