"""BENCHMARK.json against the benchmark's contract, and discovery of every
configuration, traffic mix and metric reader by name."""
import re

import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for n in names:
        assert NAME.match(n), n
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_every_metric_that_names_a_share_of_a_peak_is_in_percent():
    for m in SPEC["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_finds_its_files_and_reports_enough(cell):
    w = harness.find_cell(SPEC, cell)
    conf = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    config = harness.load_json(harness.ROOT / conf["file"])
    assert config["name"] == conf["name"]
    limits = config["check"]["limits"]
    assert {"logit_err", "bf16_grid"} <= set(limits)
    traffic = harness.load_json(harness.BENCH / "traffic"
                                / f"{w['traffic']}.json")
    assert traffic["kind"] in ("open", "closed")
    e2e = harness.cell_metrics(SPEC, cell, "end_to_end")
    layer = harness.cell_metrics(SPEC, cell, "per_layer")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in names, (cell, m["name"])
    for m in e2e + layer:
        assert callable(harness.load_reader(m["name"]))


def test_a_new_cell_is_a_new_entry():
    spec = dict(SPEC)
    new = {"name": "googlenet224-closed64", "config": "googlenet-224",
           "traffic": "closed64", "chips": 1, "why": "x"}
    spec["workloads"] = SPEC["workloads"] + [new]
    spec["end_to_end"] = [dict(m) for m in SPEC["end_to_end"]]
    for m in spec["end_to_end"]:
        if m["name"] == "images_per_s":
            m["workloads"] = m.get("workloads", []) + [new["name"]]
    names = {m["name"] for m in
             harness.cell_metrics(spec, new["name"], "end_to_end")}
    assert names == {"images_per_s", "setup_s"}
    layer = {m["name"] for m in
             harness.cell_metrics(spec, new["name"], "per_layer")}
    assert {"plan_s", "compile_s"} <= layer


def test_unknown_names_fail():
    with pytest.raises(KeyError):
        harness.find_cell(SPEC, "no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric.b1")


def test_a_cell_family_suffix_falls_back_to_the_quantity_reader():
    assert (harness.load_reader("device_idle.newcell").__module__
            == harness.load_reader("device_idle").__module__)
