"""BENCHMARK.json against the benchmark's contract: names, units, files found
by name, and which cell reports which metric."""

import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics(spec):
    return spec["end_to_end"] + spec["per_layer"]


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(spec["command"]) <= 32
    for word in spec["command"][1:]:
        assert any(word.startswith(p + "/") for p in spec["paths"])
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units_fit_the_allowed_characters(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [w["traffic"] for w in spec["workloads"]]
    names += [m["name"] for m in metrics(spec)]
    names += [k for c in spec["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in metrics(spec):
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in spec[group]]
        assert len(ns) == len(set(ns)), group
    all_metrics = [m["name"] for m in metrics(spec)]
    assert len(all_metrics) == len(set(all_metrics))
    for text in ([c["why"] for c in spec["configs"]] + [c["source"] for c in spec["configs"]]
                 + [w["why"] for w in spec["workloads"]]
                 + [m["layer"] for m in spec["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_entries_have_exactly_the_contract_keys(spec):
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])


def test_every_name_resolves_to_its_file(spec):
    for c in spec["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        with open(path) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
    assert len({c["file"] for c in spec["configs"]}) == len(spec["configs"])
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        traffic.validate(cell.mix)
    for m in spec["per_layer"]:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read)
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}


def test_each_cell_reports_setup_another_end_to_end_and_a_layer(spec):
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        e2e = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics("per_layer")


def test_each_per_layer_metric_moves_what_its_cells_report(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    layers = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
        for w in m.get("workloads", [x["name"] for x in spec["workloads"]]):
            cell = harness.load_cell(w, ROOT)
            assert m["moves"] in {x["name"] for x in cell.metrics("end_to_end")}, (
                m["name"], w)


def test_four_chip_cells_are_at_most_half(spec):
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_peaks_are_keyed_by_device_kind_with_a_source():
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5 lite" in peaks
    for kind, row in peaks.items():
        assert row["source"] and row["flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0


def test_full_check_fits_the_time_budget(spec):
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (spec["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
