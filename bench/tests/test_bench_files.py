"""Every cell of BENCHMARK.json resolves to the files the harness finds
by name, and the file keeps to the benchmark's contract."""
import json
import re

import pytest

from bench import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves(workload):
    cell = harness.load_cell(workload)
    t = cell.traffic
    for sub, name in (("drivers", t["driver"]), ("generators",
                                                  t["generator"])):
        assert (harness.BENCH / sub / f"{name}.py").is_file()
    for m in cell.end_to_end + cell.per_layer:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "tasks_per_s"}
    assert cell.per_layer
    assert set(cell.checks) >= {"fate_mismatch", "completion_rel"}


def test_names_units_and_metrics():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(CELLS)) == len(CELLS)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)
    for c in configs.values():
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg)
