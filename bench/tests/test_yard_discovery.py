"""BENCHMARK.json and the files the harness finds by name."""

import json
import re

import pytest

import harness
from yardstick import peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_every_cell_finds_its_files(spec):
    for cell in spec["workloads"]:
        cfg = harness.load_config(spec, cell["config"])
        assert cfg["name"] == cell["config"]
        assert cfg["chips"] == cell["chips"]
        harness.load_module("references", cfg["reference"])
        traffic = harness.load_traffic(cell["traffic"])
        driver = harness.load_module("drivers", traffic["driver"])
        assert hasattr(driver, "Driver") and hasattr(driver, "check")
        assert "score_rel_err" in cfg["limits"]


def test_every_per_layer_metric_has_a_reader(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert callable(harness.load_module("layers", m["name"]).read)
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            reports = {x["name"] for x in harness.metrics_for(
                spec, cell, "end_to_end")}
            assert m["moves"] in reports, (m["name"], cell)
        if m["name"].endswith("_roofline"):
            harness.load_module("rooflines", m["name"][:-len("_roofline")])


def test_every_cell_reports_setup_an_e2e_metric_and_a_layer(spec):
    for cell in spec["workloads"]:
        e2e = {m["name"] for m in harness.metrics_for(spec, cell["name"],
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(spec, cell["name"], "per_layer")


def test_names_and_paths_keep_to_the_contract(spec):
    names = [c["name"] for c in spec["configs"]] + \
        [c["name"] for c in spec["workloads"]] + \
        [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [c["traffic"] for c in spec["workloads"]]:
        assert NAME.match(n), n
    assert spec["paths"] == ["bench"]
    assert spec["command"][1].startswith("bench/")
    for c in spec["configs"]:
        assert c["file"].startswith("bench/")
    assert len(json.dumps(spec)) < 64 * 1024


def test_unknown_cell_is_an_error(spec):
    with pytest.raises(KeyError):
        harness.find_cell(spec, "no-such-cell")


def test_peaks_table_keyed_by_device_kind():
    row = peaks.peaks("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert peaks.flops_peak("TPU v5 lite", "float32") == 197e12
    assert peaks.flops_peak("TPU v5 lite", "int8") == 393e12
    assert "TPU v5e" in peaks.load()["source"]


def test_device_missing_from_peaks_table_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.flops_peak("TPU v5 lite", "float8")
