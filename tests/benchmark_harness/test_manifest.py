"""BENCHMARK.json against its contract and against the files its names lead
to; and that a new cell is files and an entry, never an edit of the harness."""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil

import pytest

from benchmark import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    return run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_keys_names_units_and_lengths(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in manifest["workloads"]]
    names += [c["name"] for c in manifest["configs"]]
    assert len(names) == len(set(names))
    for name in names + [w["traffic"] for w in manifest["workloads"]]:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for text in [w["why"] for w in manifest["workloads"]] + [c["why"] for c in manifest["configs"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(len(pairs) // 2, 1)
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(manifest):
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in manifest["end_to_end"])
    for w in manifest["workloads"]:
        cell = run.load_cell(w["name"])
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1


def test_moves_names_an_end_to_end_metric_each_of_its_cells_reports(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    end_to_end = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in end_to_end, m["name"]
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in end_to_end[m["moves"]], (m["name"], cell)


def test_every_name_leads_to_its_file_and_every_file_to_its_code(manifest):
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in manifest["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" not in folder:
                assert all(allowed.match(os.path.relpath(os.path.join(folder, f), ROOT)) for f in files)
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        config = run.load_json(os.path.join(ROOT, c["file"]))
        assert config["name"] == c["name"] and config["source"] == c["source"]
        assert config["reduced"] == c["reduced"] and config["guarantees"]
    for w in manifest["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.traffic["config"] == w["config"] and cell.chips == w["chips"]
        assert hasattr(importlib.import_module(f"benchmark.traffic.{cell.traffic['driver']}"), "Traffic")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        spec = run.load_metric(m["name"])  # the reader and its parameters, nothing kept twice
        assert set(spec) == {"reducer", "params", "about"}, m["name"]
        assert callable(importlib.import_module(f"benchmark.reducers.{spec['reducer']}").read)
    # what ships ahead of its cell: the slot driver, its traffic and its legs
    for name in ("slot_verify_ms", "slot_aggregate_ms", "slot_reroot_ms"):
        assert run.load_metric(name)["reducer"] == "hist_mean_ms"
    assert os.path.exists(os.path.join(ROOT, "benchmark", "workloads", "slot_2p20.sync.json"))


def test_a_new_cell_is_files_and_an_entry_and_no_edit(manifest, tmp_path):
    source = open(os.path.join(ROOT, "benchmark", "run.py")).read()
    for entry in manifest["configs"] + manifest["workloads"] + manifest["end_to_end"] + manifest["per_layer"]:
        whole = r"(?<![A-Za-z0-9_.])" + re.escape(entry["name"]) + r"(?![A-Za-z0-9_.])"
        assert not re.search(whole, source), entry["name"]
    # a later PR's cell: one more traffic file and one more entry
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = run.load_json(os.path.join(ROOT, "benchmark", "workloads", "blob_block_6.verify.json"))
    traffic["params"]["invalid_every"] = 1
    (root / "benchmark" / "workloads" / "blob_block_6.invalid.json").write_text(json.dumps(traffic))
    later = dict(manifest, workloads=manifest["workloads"] + [
        {"name": "blob_block_6.invalid", "config": "deneb_blobs_6", "traffic": "invalid",
         "chips": 1, "why": "a wrong proof in every block"}])
    (root / "BENCHMARK.json").write_text(json.dumps(later))
    cell = run.load_cell("blob_block_6.invalid", root=str(root))
    assert cell.traffic["params"]["invalid_every"] == 1
    assert {m["name"] for m in cell.end_to_end} == {"verdict_ms", "setup_s"}
    assert "device_idle_pct" in {m["name"] for m in cell.per_layer}
    with pytest.raises(run.BenchError):
        run.load_cell("no.such.cell")
