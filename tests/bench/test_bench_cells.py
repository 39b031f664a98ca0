"""Each cell, driven on the CPU at a tiny size, is correct as
it stands and not correct with its timed path broken: computed in
bfloat16 (the control) or with one answer altered where it is made.
A configuration that the benchmark does not hold yet runs the same way
from new files and ``BENCHMARK.json`` entries alone."""
import json
import shutil

import pytest

import bench_cpu_run as B

CELLS = [c["name"] for c in B.R.manifest()["workloads"]]
RUNS = ["sound", "control", "answer"]


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


# -- a configuration added as data ------------------------------------------

#: a DCI 2K-like 5/3 configuration, and 16-bit unsigned samples (a
#: Sentinel-2 band's container); neither is in the benchmark
NEW = {
    "new-2k-j2k53": dict(shape=[3, 1080, 2048], bit_depth=10,
                         dtype="float32", wavelet="cdf53", levels=3,
                         cpu={"shape": [2, 16, 32], "levels": 3}),
    "new-u16-j2k97": dict(shape=[10980, 10980], bit_depth=16,
                          samples="uint16", dtype="float32",
                          wavelet="cdf97", levels=2,
                          cpu={"shape": [32, 64], "levels": 2}),
}


def _add_configuration(root, monkeypatch, name: str, traffics) -> list:
    """A copy of the benchmark's files under ``root`` with configuration
    ``name`` (``NEW``) and its cells added: a new configuration file and
    new ``BENCHMARK.json`` entries, nothing else.  Returns the cells."""
    shutil.copytree(B.R.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = dict(name=name, source="a test configuration",
               scheme="ns-polyconv", backend="auto", fuse="levels",
               chips=1, reduced={}, limits={"rel_err": 3e-05}, **NEW[name])
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(cfg))
    bench = B.R.manifest()
    bench["configs"].append({"name": name, "source": cfg["source"],
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "a test"})
    cells = [f"{name}.{t}" for t in traffics]
    for cell, traffic in zip(cells, traffics):
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": traffic, "chips": 1,
                                   "why": "a test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"].endswith(f".{traffic}") or \
                    m["name"] == f"{traffic}_mpix_s":
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(B.R, "BENCH", root / "bench")
    monkeypatch.setattr(B.R, "MANIFEST", root / "BENCHMARK.json")
    return cells


def _dwt2_taking_bit_depth(monkeypatch):
    """Stands in for an engine ``dwt2`` that takes integer samples with
    their ``bit_depth``: the level shift to float32, then ``dwt2``."""
    import jax.numpy as jnp

    from repro import core
    plain = core.dwt2

    def dwt2(x, *, bit_depth, **kw):
        return plain(x.astype(jnp.float32) - 2.0 ** (bit_depth - 1), **kw)

    monkeypatch.setattr(core, "dwt2", dwt2)


@pytest.mark.parametrize("run", RUNS)
@pytest.mark.parametrize("cell", ["new-2k-j2k53.encode",
                                  "new-2k-j2k53.decode",
                                  "new-u16-j2k97.encode"])
def test_new_configuration_runs_from_data_files_alone(cell, run, tmp_path,
                                                      monkeypatch):
    name, traffic = cell.split(".")
    assert cell in _add_configuration(tmp_path, monkeypatch, name,
                                      [traffic])
    if "samples" in NEW[name]:
        _dwt2_taking_bit_depth(monkeypatch)
    res = B.run_cell(cell, monkeypatch, control=run == "control",
                     fault="answer" if run == "answer" else "")
    assert res["correct"] == (run == "sound"), res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {f"{traffic}_mpix_s", "setup_s"}


def test_decode_refuses_integer_samples(tmp_path, monkeypatch):
    cell, = _add_configuration(tmp_path, monkeypatch, "new-u16-j2k97",
                               ["decode"])
    with pytest.raises(B.R.BenchError, match="float samples.*uint16"):
        B.run_cell(cell, monkeypatch)


def test_configuration_without_a_cpu_size_is_named(tmp_path, monkeypatch):
    cell, = _add_configuration(tmp_path, monkeypatch, "new-2k-j2k53",
                               ["encode"])
    path = tmp_path / "bench" / "configs" / "new-2k-j2k53.json"
    cfg = json.loads(path.read_text())
    del cfg["cpu"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(B.R.BenchError, match="'new-2k-j2k53'.*'cpu'"):
        B.run_cell(cell, monkeypatch)
