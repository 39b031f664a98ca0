"""Everything a cell needs is found by name, and BENCHMARK.json keeps to
the shape the harness reads."""
import json
import math
import re

import pytest

from bench import run as R

BENCH = R.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_resolves_by_name(cell):
    cfg = R.load_config(cell["config"])
    assert cfg["name"] == cell["config"]
    assert cfg["chips"] == cell["chips"]
    traffic = R.load_traffic(cell["traffic"])
    driver = R.load_driver(traffic["driver"])
    for fn in ("setup", "run", "check"):
        assert callable(getattr(driver, fn))
    for traced in (False, True):
        for m in R.cell_metrics(cell, BENCH, traced):
            if traced:
                assert callable(R.load_metric(m["name"]).read)


@pytest.mark.parametrize("loader,name", [
    (R.load_config, "no-such-config"), (R.load_traffic, "no_such_mix"),
    (R.load_driver, "no_such_driver"), (R.load_metric, "no_such.metric"),
    (R.find_cell, "no-such.cell")])
def test_unknown_names_raise(loader, name):
    with pytest.raises(R.BenchError, match=re.escape(name.split(".")[0])):
        loader(name)


def test_manifest_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1] == "bench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in BENCH[k]}) == len(BENCH[k])
        for m in BENCH[k]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    cells = [c["name"] for c in BENCH["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in BENCH["end_to_end"]}
    for cell in BENCH["workloads"]:
        mine = {m["name"] for m in R.cell_metrics(cell, BENCH, False)}
        assert "setup_s" in mine and len(mine) >= 2
        assert R.cell_metrics(cell, BENCH, True)
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(cells)
        assert set(m["workloads"]) <= set(e2e[m["moves"]]), m["name"]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= max(
        1, len(cells) // 2)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_hold_the_run_shapes(cfg):
    c = json.loads((R.ROOT / cfg["file"]).read_text())
    h, w = c["shape"][-2:]
    assert h % (1 << c["levels"]) == 0 and w % (1 << c["levels"]) == 0
    assert set(cfg["reduced"]) == set(c["reduced"])
    assert c["limits"]["rel_err"] > 0


def test_no_harness_code_names_a_configuration():
    """A configuration is data: the harness's code and the CPU driver of
    the tests never name one, so a later configuration needs no edit."""
    files = sorted(R.BENCH.rglob("*.py")) + [
        R.ROOT / "tests" / "bench" / "bench_cpu_run.py"]
    names = [c["name"] for c in BENCH["configs"]]
    named = [(f.relative_to(R.ROOT).as_posix(), n) for f in files
             for n in names if n in f.read_text()]
    assert len(files) > 10 and named == []


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_state_a_cpu_size(cfg):
    c = json.loads((R.ROOT / cfg["file"]).read_text())
    h, w = c["cpu"]["shape"][-2:]
    assert h % (1 << c["cpu"]["levels"]) == 0
    assert w % (1 << c["cpu"]["levels"]) == 0
    assert math.prod(c["cpu"]["shape"]) <= 1 << 16    # not a chip size
