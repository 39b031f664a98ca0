"""Each cell, driven on the CPU at a tiny size, is correct as
it stands and not correct with its timed path broken: computed in
bfloat16 (the control) or with one answer altered where it is made."""
import pytest

import bench_cpu_run as B

CELLS = [c["name"] for c in B.R.manifest()["workloads"]]


@pytest.fixture(autouse=True)
def _bench_out(tmp_path, monkeypatch):
    monkeypatch.setattr(B.R, "OUT", tmp_path)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    res = B.run_cell(cell, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("how", ["control", "answer"])
def test_broken_path_is_not_correct(cell, how, monkeypatch):
    res = B.run_cell(cell, monkeypatch, control=how == "control",
                     fault="" if how == "control" else how)
    assert not res["correct"]
    assert res["checks"]["rel_err"]["value"] > \
        res["checks"]["rel_err"]["limit"]
