"""The reduction from a profiler trace to busy, idle and kernel time,
on hand-made records and on a trace recorded on a
TPU v5e (``data/``)."""
import glob
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps():
    merged = TR.union([(5, 9), (0, 2), (1, 3), (9, 10), (12, 15)])
    assert merged == [(0, 3), (5, 10), (12, 15)]
    assert TR.length(merged) == 11
    assert TR.gaps(merged, 2, 13) == [(3, 5), (10, 12)]
    assert TR.gaps([], 0, 4) == [(0, 4)]


def test_op_grouping_and_gap_labels():
    hlo = ("%fusion.5 = f32[3,1080,2048]{2,1,0:T(8,128)} fusion(f32[3,"
           "2160,4096]{2,1,0} %p0), kind=kLoop")
    assert TR.op_label(hlo) == "fusion.5 fusion f32[3,1080,2048]"
    assert TR.op_label("unparsed event") == "unparsed event"
    host = [("bench.window", 0, 100), ("bench.wait", 10, 30),
            ("serve.stack_h2d", 25, 60)]
    assert TR.label((20, 28), host) == "bench.wait"
    assert TR.label((30, 60), host) == "serve.stack_h2d"
    assert TR.label((80, 90), host) == "none"


def _records():
    # two devices over a window 0..100 ns: device 0 busy 0-40 (a kernel
    # 10-30 inside a fusion 0-40), device 1 busy 50-60; an op outside
    # the window is cut away
    return {"devices": {
        "0": [["fusion.1", 0, 40, False], ["custom-call.2", 10, 30, True],
              ["copy.3", 90, 130, False]],
        "1": [["collective-permute.4", 50, 60, False]]},
        "host": [["bench.window", 0, 100], ["bench.encode", 40, 90]]}


def test_reduce_hand_made_records():
    s = TR.reduce(_records(), [0, 1])
    assert s.devices == 2
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((50 + 10) / 2 * 1e-9)
    assert s.kernel_s == pytest.approx(20 / 2 * 1e-9)
    assert s.idle_share == pytest.approx(0.7)
    assert s.op_s["copy.3"] == pytest.approx(10 / 2 * 1e-9)
    top = s.breakdown()["idle_gaps"][0]
    assert top == ["bench.encode", pytest.approx(50e-9)]


def test_reduce_refuses_a_trace_with_no_device_op():
    with pytest.raises(ValueError, match="no op"):
        TR.reduce({"devices": {}, "host": []}, [0])


def test_load_reads_host_annotations_from_an_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda v: v * 2.0)
    x = jnp.ones((64, 64))
    jax.block_until_ready(f(x))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.encode"):
                jax.block_until_ready(f(x))
    rec = TR.load(tmp_path)
    names = [h[0] for h in rec["host"]]
    assert "bench.window" in names and "bench.encode" in names
    assert rec["devices"] == {}          # a CPU has no TPU plane
    TR.save(rec, tmp_path / "r.json.gz")
    assert TR.read_saved(tmp_path / "r.json.gz") == rec


CHIP = sorted(glob.glob(str(DATA / "*.json.gz")))


@pytest.mark.parametrize("path", CHIP, ids=lambda p: Path(p).name)
def test_reduce_chip_trace(path):
    rec = TR.read_saved(path)
    s = TR.reduce(rec)
    assert 0 < s.busy_s <= s.window_s
    assert 0 < s.kernel_s <= s.busy_s
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    assert sum(s.op_s.values()) >= s.busy_s * (1 - 1e-9)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(g[0].startswith(("bench.", "serve.", "execute.", "none"))
               for g in b["idle_gaps"])


def test_dci4k_encode_trace_readings():
    """A DCI 4K encode window on one v5e: four strided-slice gathers
    (``to_planes``) hold the device; the Pallas kernels under 1 %."""
    s = TR.reduce(TR.read_saved(DATA / "v5e_dci4k_encode.json.gz"), [0])
    assert (s.window_s, s.busy_s, s.kernel_s) == (
        5.188526571, 5.185959002, 0.037552561)
    top = s.breakdown()["device_ops"]
    assert [n for n, _ in top[:4]] == [
        "fusion fusion f32[2211840,3]", "fusion.1 fusion f32[2211840,3]",
        "fusion.3 fusion f32[2211840,3]", "fusion.2 fusion f32[2211840,3]"]
    assert s.breakdown()["idle_gaps"][0] == ["bench.encode", 0.001189739]


def test_load_reads_a_tpu_xplane():
    """The raw trace of two small encodes and decodes on one v5e: op
    events of TPU 0, Mosaic custom calls flagged as kernels, and the
    host annotations and mirrored spans."""
    rec = TR.load(DATA)
    ops = rec["devices"]["0"]
    assert len(ops) == 286 and sum(k for *_, k in ops) == 8
    assert all(n.split(" ")[1] == "custom-call" for n, _, _, k in ops if k)
    assert {h[0] for h in rec["host"]} == {
        "bench.encode", "bench.decode", "execute.forward",
        "execute.inverse"}
    s = TR.reduce(rec, [0])
    assert (s.busy_s, s.kernel_s) == (0.000234249, 1.3552e-05)
