"""BENCHMARK.json keeps to its contract, and every name it gives is a file."""
import json
import re

from conftest import BENCH

ROOT = BENCH.parent
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert BENCH_JSON["paths"] == ["bench"]
    assert 1 <= BENCH_JSON["run_seconds"] <= 51


def test_names_and_units():
    entries = (BENCH_JSON["configs"] + BENCH_JSON["workloads"]
               + BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in BENCH_JSON["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in BENCH_JSON["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH_JSON[group]]
        assert len(names) == len(set(names)), group


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in BENCH_JSON["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def _reports(workload):
    return {m["name"] for m in BENCH_JSON["end_to_end"]
            if workload in m.get("workloads", [workload])}


def test_per_layer_moves_an_end_to_end_metric_of_its_cells():
    e2e = {m["name"] for m in BENCH_JSON["end_to_end"]}
    cells = {w["name"] for w in BENCH_JSON["workloads"]}
    for m in BENCH_JSON["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s", m["name"]
        assert m["layer"] and "\n" not in m["layer"]
        for w in m["workloads"]:
            assert w in cells, (m["name"], w)
            assert m["moves"] in _reports(w), (m["name"], w)
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_every_cell_reports_enough():
    for w in BENCH_JSON["workloads"]:
        rep = _reports(w["name"])
        assert "setup_s" in rep and len(rep) >= 2, w["name"]
        layer = [m for m in BENCH_JSON["per_layer"] if w["name"] in m["workloads"]]
        assert layer, w["name"]


def test_every_name_is_a_file():
    configs = {c["name"]: c for c in BENCH_JSON["configs"]}
    for c in configs.values():
        path = ROOT / c["file"]
        assert path.is_file() and path.is_relative_to(BENCH)
        assert json.loads(path.read_text())["name"] == c["name"]
    for w in BENCH_JSON["workloads"]:
        assert w["config"] in configs
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
        limits = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        assert limits and all(v >= 0 for v in limits.values())
    used = {w["config"] for w in BENCH_JSON["workloads"]}
    assert used == set(configs)


def test_command_stays_inside_paths():
    cmd = BENCH_JSON["command"]
    assert cmd[0] == "python3" and (ROOT / cmd[1]).is_file()
    assert cmd[1].startswith("bench/") and len(cmd) <= 32
