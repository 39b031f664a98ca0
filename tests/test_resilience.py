"""Resilience integration: faults riding through the real stack.

End-to-end coverage of PR 9's recovery contract (docs/resilience.md):
retry-in-place recovery is bit-identical on the deterministic path,
degradation re-resolves the plan down the capability chain and verifies
against the jnp reference, silent corruption is detected and retried,
streaming transforms checkpoint/resume across kills, and the persistent
stores survive torn writes.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import dwt2, idwt2
from repro.core.transform import validate_finite
from repro.engine.pyramid import Pyramid
from repro.faults import degrade as DG
from repro.faults import inject as FJ
from repro.faults import plan as FP
from repro.faults.degrade import (FALLBACKS, DegradationExhausted,
                                  ExactnessError)


# the plane is disarmed around every test by
# tests/conftest.py::_isolated_planes

pytestmark = pytest.mark.chaos


def _arm(text, seed=0):
    return FJ.activate(FP.FaultPlan.from_text(text, seed=seed))


def _img(shape=(64, 64), seed=0):
    return np.random.default_rng(seed).standard_normal(shape) \
        .astype(np.float32)


# -- executor dispatch: retry + degrade -------------------------------

def test_transient_fault_retried_bit_identical():
    x = _img()
    ref = np.asarray(dwt2(x, levels=2).ll)
    _arm("execute.forward=once")
    pyr = dwt2(x, levels=2)
    assert np.array_equal(np.asarray(pyr.ll), ref)


def test_corruption_detected_and_retried_bit_identical():
    x = _img(seed=1)
    ref = np.asarray(dwt2(x, levels=2).ll)
    _arm("execute.forward=corrupt:once")
    pyr = dwt2(x, levels=2)          # poisoned attempt rejected, retried
    assert np.array_equal(np.asarray(pyr.ll), ref)
    assert not np.isnan(np.asarray(pyr.ll)).any()


def test_persistent_failure_degrades_and_records_fallback():
    x = _img(seed=2)
    ref = np.asarray(dwt2(x, levels=2, backend="jnp", fuse="none").ll)
    before = {(s["labels"]["from"], s["labels"]["to"]): s["value"]
              for s in FALLBACKS.series()}
    _arm("pyramid.launch=always")
    pyr = dwt2(x, levels=2, backend="pallas", fuse="pyramid")
    FJ.activate(None)
    assert np.allclose(np.asarray(pyr.ll), ref, rtol=1e-3, atol=1e-4)
    after = {(s["labels"]["from"], s["labels"]["to"]): s["value"]
             for s in FALLBACKS.series()}
    hop = ("pallas/pyramid", "pallas/levels")
    assert after.get(hop, 0) > before.get(hop, 0)
    labels = [s["labels"] for s in FALLBACKS.series()]
    assert all({"from", "to", "site"} <= set(lb) for lb in labels)


def test_reference_path_exhausts_chain_with_cause():
    x = _img(seed=3)
    _arm("execute.forward=always")
    with pytest.raises(DegradationExhausted) as ei:
        dwt2(x, levels=1, backend="jnp", fuse="none")
    assert isinstance(ei.value.__cause__, FJ.InjectedFault)


def test_resilience_off_restores_fail_fast(monkeypatch):
    monkeypatch.setattr(
        DG, "CONFIG", dataclasses.replace(DG.CONFIG, enabled=False))
    _arm("execute.forward=always")
    with pytest.raises(FJ.InjectedFault):
        dwt2(_img(seed=4), levels=1)


def test_inverse_dispatch_recovers_too():
    x = _img(seed=5)
    pyr = dwt2(x, levels=2)
    ref = np.asarray(idwt2(pyr))
    _arm("execute.inverse=once")
    out = idwt2(pyr)
    assert np.array_equal(np.asarray(out), ref)


def test_compile_failure_propagates_without_retry_or_degradation(
        monkeypatch):
    """A plan whose own kernel fails to lower or compile is a defect: it
    raises, and no other backend serves it in its place.  A runtime
    fault on the same plan still walks the degradation chain."""
    from repro import engine
    from repro.engine import executor as X
    from repro.faults.policy import RETRIES

    class Refused(RuntimeError):
        pass

    def refuse(*args, **kw):
        raise Refused("kernel refused by the compiler")

    x = _img((48, 40), seed=7)
    cache = engine.PlanCache()
    plan = engine.get_plan(shape=x.shape, levels=2, backend="pallas",
                           fuse="levels", cache=cache)
    monkeypatch.setattr(X, "pallas_level_forward", refuse)
    with pytest.raises(DG.KernelCompileError) as ei:
        plan.execute(x)
    assert isinstance(ei.value.__cause__, Refused)
    assert sum(s["value"] for s in FALLBACKS.series()) == 0
    assert sum(s["value"] for s in RETRIES.series()) == 0
    monkeypatch.undo()

    runtime = engine.get_plan(shape=x.shape, levels=2, backend="pallas",
                              fuse="levels", cache=cache)
    ref = np.asarray(dwt2(x, levels=2, backend="jnp").ll)
    _arm("execute.forward=always")
    out = runtime.execute(x)
    FJ.activate(None)
    assert np.allclose(np.asarray(out.ll), ref, rtol=1e-3, atol=1e-4)
    assert sum(s["value"] for s in FALLBACKS.series()) == 1


def test_engine_stats_faults_section_live():
    from repro import engine
    _arm("execute.forward=once")
    dwt2(_img(seed=6), levels=1)
    s = engine.stats()["faults"]
    assert s["active"] and s["injections"] >= 1
    assert s["retries"] >= 1
    FJ.activate(None)
    assert engine.stats()["faults"]["active"] is False


# -- input validation (validate="nan") --------------------------------

def test_validate_nan_rejects_bad_inputs_and_pyramids():
    x = _img()
    x[3, 7] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        dwt2(x, levels=1, validate="nan")
    with pytest.raises(ValueError, match="validate"):
        dwt2(_img(), levels=1, validate="bogus")
    pyr = dwt2(_img(), levels=1)
    bad_ll = np.asarray(pyr.ll).copy()
    bad_ll[0, 0] = np.inf
    bad = Pyramid(ll=bad_ll, details=pyr.details)
    with pytest.raises(ValueError, match="non-finite"):
        idwt2(bad, validate="nan")
    # default stays permissive (no device-sync sweep on the hot path)
    assert dwt2(x, levels=1) is not None
    assert validate_finite(_img(), None) is None


# -- streaming checkpoint / resume ------------------------------------

def _stream_kw():
    return dict(levels=2, tiles=(32, 32), backend="jnp", fuse="none")


def test_stream_checkpoint_resume_recomputes_unjournaled_bands(tmp_path):
    from repro.tiling import open_checkpoint, stream_dwt2
    img = np.arange(128.0 * 128, dtype=np.float32).reshape(128, 128)
    ref = stream_dwt2(img, **_stream_kw())
    ck = str(tmp_path / "ck")
    pyr = stream_dwt2(img, checkpoint=ck, **_stream_kw())
    assert np.array_equal(np.asarray(pyr.ll), np.asarray(ref.ll))

    # simulate a kill after band 1: truncate the journal to 2 records
    # and scribble garbage over a non-journaled band's output rows —
    # resume must trust ONLY journaled bands and recompute the rest
    jp = os.path.join(ck, "journal.jsonl")
    lines = open(jp).read().splitlines()
    assert len(lines) == 4                      # 4 bands of 32 rows
    with open(jp, "w") as f:
        f.write("\n".join(lines[:2]) + "\n")
    man = json.load(open(os.path.join(ck, "manifest.json")))["config"]
    ck2 = open_checkpoint(ck, man)
    assert ck2.completed == {0, 1} and not ck2.complete
    ck2.ll[16:] = -777.0                        # bands 2-3 ll rows poisoned
    ck2.ll.flush()

    pyr2 = stream_dwt2(img, checkpoint=ck, **_stream_kw())
    assert np.array_equal(np.asarray(pyr2.ll), np.asarray(ref.ll))
    for da, db in zip(pyr2.details, ref.details):
        for a, b in zip(da, db):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_stream_checkpoint_mismatch_and_torn_journal(tmp_path):
    from repro.tiling import (CheckpointMismatch, open_checkpoint,
                              stream_dwt2)
    img = np.zeros((128, 128), np.float32)
    ck = str(tmp_path / "ck")
    stream_dwt2(img, checkpoint=ck, **_stream_kw())
    with pytest.raises(CheckpointMismatch, match="levels"):
        stream_dwt2(img, checkpoint=ck,
                    **dict(_stream_kw(), levels=1))
    with open(os.path.join(ck, "journal.jsonl"), "a") as f:
        f.write('{"band": 2, "crc": 1}\n')     # checksum-invalid record
        f.write('{"band": 3, "cr')             # torn tail
    man = json.load(open(os.path.join(ck, "manifest.json")))["config"]
    ck2 = open_checkpoint(ck, man)
    assert ck2.stats()["torn_records"] == 2
    assert ck2.completed == {0, 1, 2, 3}       # the valid prefix


def test_stream_retries_ride_transient_band_faults():
    from repro.tiling import stream_dwt2
    img = np.arange(128.0 * 128, dtype=np.float32).reshape(128, 128)
    ref = stream_dwt2(img, **_stream_kw())
    _arm("stream.host_gather=0.3,stream.drain=0.3", seed=11)
    pyr = stream_dwt2(img, retries=4, **_stream_kw())
    FJ.activate(None)
    assert np.array_equal(np.asarray(pyr.ll), np.asarray(ref.ll))
    _arm("stream.h2d_dispatch=once", seed=1)
    with pytest.raises(FJ.InjectedFault):       # retries=0: fail fast
        stream_dwt2(img, **_stream_kw())


# -- crash-safe stores ------------------------------------------------

def test_trace_store_checksums_detect_torn_and_mutated_lines(tmp_path):
    from repro.profiler import store as S
    p = tmp_path / "t.jsonl"
    st = S.TraceStore(p)
    rec = S.TraceRecord(
        fingerprint="cpu:test", wavelet="cdf97", scheme="ns-polyconv",
        levels=2, shape=(64, 64), dtype="float32", backend="jnp",
        optimize=False, fuse="none", boundary="periodic",
        compute_dtype="float32", tap_opt="full", tiles=None, block=None,
        time_s=0.01, hbm_bytes=1000, launches=4)
    st.extend([rec, rec])
    line = open(p).readline()
    assert "crc" in json.loads(line)

    legacy = json.loads(line)
    legacy.pop("crc")
    mutated = json.loads(line)
    mutated["time_s"] = 99.0                    # stale crc
    with open(p, "a") as f:
        f.write(json.dumps(legacy, sort_keys=True) + "\n")
        f.write(json.dumps(mutated, sort_keys=True) + "\n")
        f.write('{"v": 1, "torn...\n')
    before = {s_["labels"]["reason"]: s_["value"]
              for s_ in S.CORRUPT_RECORDS.series()}
    st2 = S.TraceStore(p)
    recs = st2.records()
    assert len(recs) == 3                       # 2 crc'd + 1 legacy
    assert not any(r.time_s == 99.0 for r in recs)
    after = {s_["labels"]["reason"]: s_["value"]
             for s_ in S.CORRUPT_RECORDS.series()}
    assert after.get("checksum", 0) == before.get("checksum", 0) + 1
    assert after.get("parse", 0) == before.get("parse", 0) + 1


def test_block_table_save_is_atomic(tmp_path, monkeypatch):
    from repro import ioutil
    from repro.engine import autotune as AT
    p = tmp_path / "BLOCK_TABLE.json"
    AT.save_entry("ns-polyconv", (64, 64), "none", "jnp", (8, 8),
                  path=p, fingerprint="cpu:x")
    AT.save_entry("ns-polyconv", (32, 32), "none", "jnp", (4, 4),
                  path=p, fingerprint="cpu:x")
    table = json.load(open(p))
    assert len(table) == 2
    # no leftover temp files from the atomic writes
    assert [f for f in os.listdir(tmp_path)] == ["BLOCK_TABLE.json"]

    calls = {"n": 0}
    real = ioutil.atomic_write_text

    def crash(path, text):
        calls["n"] += 1
        raise OSError("disk gone")
    monkeypatch.setattr(ioutil, "atomic_write_text", crash)
    with pytest.raises(OSError):
        AT.save_entry("ns-polyconv", (16, 16), "none", "jnp", (2, 2),
                      path=p, fingerprint="cpu:x")
    monkeypatch.setattr(ioutil, "atomic_write_text", real)
    assert json.load(open(p)) == table          # old table intact


def test_atomic_write_text_replaces_not_appends(tmp_path):
    from repro import ioutil
    p = str(tmp_path / "f.json")
    ioutil.atomic_write_text(p, "old content")
    ioutil.atomic_write_text(p, "new")
    assert open(p).read() == "new"
    assert os.listdir(tmp_path) == ["f.json"]   # temp cleaned up
