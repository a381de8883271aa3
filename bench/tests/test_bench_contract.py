"""BENCHMARK.json against the benchmark's contract, and discovery by name:
every cell finds its configuration, reference module, traffic mix and
driver, and every metric its reader, from the names alone."""

import json
import os
import re

import pytest

from bench.lib import harness

ROOT = harness.ROOT
BENCH = harness.BENCH
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate|latent|d_state|"
                   r"state_size|proj|head|expan|experts_per_tok|d_model")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and len(SPEC["command"]) <= 32
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w for w in SPEC["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_uniqueness():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files_by_name(cell):
    w = next(x for x in SPEC["workloads"] if x["name"] == cell)
    entry = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert entry["file"].startswith("bench/configs/")
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    model = harness.load_module(os.path.join(ROOT, entry["file"])[:-5] + ".py")
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             w["traffic"] + ".json"))
    driver = harness.load_module(os.path.join(BENCH, "drivers",
                                              traffic["driver"] + ".py"))
    assert callable(driver.run) and model.__doc__
    assert set(traffic["limits"]) <= set(harness.merged(traffic, True)["limits"])
    assert cfg["reduced"] == entry["reduced"]
    assert all(k in cfg for k in entry["reduced"])
    assert not [k for k in entry["reduced"] if WIDTH.search(k)]


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_finds_its_reader(metric):
    reader = harness.load_module(os.path.join(BENCH, "metrics", metric + ".py"))
    assert callable(reader.read) and reader.__doc__.startswith(metric)


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in CELLS:
        mine = [m for m in SPEC["end_to_end"]
                if cell in m.get("workloads", CELLS)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", CELLS) for m in SPEC["per_layer"])
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert all(w in e2e[m["moves"]].get("workloads", CELLS)
                   for w in m["workloads"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])
    assert all(1 <= len(name) <= 200 and "\n" not in name for name in layers)


def test_merged_lays_tiny_over_nested_groups():
    base = {"a": 1, "limits": {"x": 1, "y": 2}, "tiny": {"limits": {"x": 0}}}
    assert harness.merged(base, True) == {"a": 1, "limits": {"x": 0, "y": 2}}
    assert harness.merged(base, False) == {"a": 1, "limits": {"x": 1, "y": 2}}
