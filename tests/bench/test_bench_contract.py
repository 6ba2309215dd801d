"""``BENCHMARK.json`` keeps the shape the benchmark's readers and checks
rely on: names, units, files found by name, bounds and run length."""
import json
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_units_and_lines(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for x in bench["configs"] + bench["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]


def test_every_file_is_found_by_name(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        family = os.path.join(ROOT, "bench", "families", conf["model_type"] + ".py")
        assert os.path.isfile(family), (c["name"], family)
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        for path in (("workloads", w["name"]), ("traffic", w["traffic"])):
            assert os.path.isfile(os.path.join(ROOT, "bench", path[0], path[1] + ".json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        base = m["name"].split(".")[0]
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics", base + ".py")), m["name"]


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e_names = set(e2e)
    for w in bench["workloads"]:
        own = [n for n, m in e2e.items() if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in own and len(own) >= 2
        layers = [m for m in bench["per_layer"] if w["name"] in m.get("workloads", [])]
        assert layers and all(m["moves"] in own for m in layers)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e_names


def test_run_length_fits_a_full_check_of_24_cells(bench):
    s = bench["run_seconds"]
    assert 1 <= s <= 51
    cells = 24
    assert (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200 <= 43200
