"""BENCHMARK.json keeps the benchmark's contract, and every name in it
resolves to its own file: a configuration, a traffic mix, a per-layer
reader and each cell's limits."""
import json
import os
import re

import pytest

from bench.tests.common import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s"}


def test_every_name_has_its_file(bench):
    for c in bench["configs"]:
        path = os.path.join(ROOT, c["file"])
        with open(path) as f:
            conf = json.load(f)
        assert c["file"].startswith("bench/")
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) <= set(conf["field"])
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        for p in (("traffic", f"{w['traffic']}.json"),
                  ("limits", f"{w['name']}.json")):
            assert os.path.exists(os.path.join(ROOT, "bench", *p)), p
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))


def test_every_cell_reports_enough(bench):
    from bench import harness
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bench, cell,
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(bench, cell, "per_layer")
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e_names
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_run_seconds_fit_a_full_check(bench):
    """24 cells at this length fit the check's 43200 s."""
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
