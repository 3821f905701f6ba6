"""The metrics that split `setup_s` (layer `set-up`): the reducer on
histograms small enough to add by hand, the six entries of BENCHMARK.json
against their files, and the state-root cell's per-layer line on the CPU
rehearsal, where the five have to add up to the set-up they split."""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.reducers import setup_hist_sum_s

from .tiny import ROOT

STATE_ROOT, ATTS = "state_root_2p20.recompute", "block_atts_128.verify"
XLA = ["setup_trace_s", "setup_lower_s", "setup_cache_read_s", "setup_compile_s"]
NEW = [*XLA, "setup_keys_s", "setup_unnamed_s"]


def hist(*samples_ms: float) -> dict:
    return {"count": len(samples_ms), "sum": float(sum(samples_ms))}


def window_with(hists: dict, setup_seconds: float = 75.0) -> run.Window:
    w = run.Window(run.Cell("c", 1, {}, {}, [], []), "TPU v5 lite", setup_seconds=setup_seconds)
    w.hist_before = hists
    # what the window added is not set-up's: the reader must not look here
    w.hist_after = {name: hist(1e9) for name in hists}
    return w


WARM = {
    "xla.trace_ms.none": hist(1500.0, 500.0),
    "xla.trace_ms.g1_msm.call": hist(8000.0),
    "xla.lower_ms.none": hist(700.0),
    "xla.lower_ms.g1_msm.call": hist(4300.0),
    "xla.cache_read_ms.g1_msm.call": hist(20000.0, 10000.0),
    "xla.compile_ms.g1_msm.call": hist(20100.0, 10100.0),
    "xla.compile_ms.none": hist(300.0, 200.0),
    "serve.setup_ms.register_pubkeys": hist(12000.0),
    "serve.setup_ms.key_table.to_device": hist(3000.0),
    "serve.stage_ms.total": hist(90.0),
}


# ---- the reducer ---------------------------------------------------------------


@pytest.mark.parametrize("metric, seconds", [
    ("setup_trace_s", 10.0),  # over a prefix: every leg's histogram
    ("setup_lower_s", 5.0),
    ("setup_cache_read_s", 30.0),
    ("setup_compile_s", 0.7),  # `less`: every backend compile less the reads inside them
    ("setup_keys_s", 15.0),  # exact names are prefixes too
    ("setup_unnamed_s", 75.0 - (10.0 + 5.0 + 30.7 + 15.0)),  # `of_setup`
])
def test_each_metrics_params_on_a_warm_set_up_added_by_hand(metric, seconds, capsys):
    spec = run.load_metric(metric)
    assert setup_hist_sum_s.read(window_with(WARM), spec["params"]) == pytest.approx(seconds)
    err = capsys.readouterr().err.splitlines()
    assert err and all(line.startswith("set-up ") for line in err) and len(err) <= 10
    if metric == "setup_trace_s":  # the largest first, by name: the leg the seconds sat under
        assert err[0] == "set-up 8.000 s: xla.trace_ms.g1_msm.call (1)"
        assert err[1] == "set-up 2.000 s: xla.trace_ms.none (2)"


def test_the_six_add_up_to_set_up():
    values = [setup_hist_sum_s.read(window_with(WARM), run.load_metric(m)["params"]) for m in NEW]
    assert sum(values) == pytest.approx(75.0)


def test_seconds_filed_twice_read_negative_not_zero():
    less_than_its_reads = dict(WARM, **{"xla.compile_ms.g1_msm.call": hist(100.0)})
    params = run.load_metric("setup_compile_s")["params"]
    assert setup_hist_sum_s.read(window_with(less_than_its_reads), params) == pytest.approx(-29.4)
    # named seconds beyond the wall (a phase inside another, two threads compiling at once)
    params = run.load_metric("setup_unnamed_s")["params"]
    assert setup_hist_sum_s.read(window_with(WARM, setup_seconds=20.0), params) \
        == pytest.approx(20.0 - 60.7)


def test_a_family_without_a_sample_reads_zero_where_the_listener_ran():
    cold = {k: v for k, v in WARM.items() if "cache_read" not in k and "setup_ms" not in k}
    assert setup_hist_sum_s.read(window_with(cold), run.load_metric("setup_cache_read_s")["params"]) == 0.0
    assert setup_hist_sum_s.read(window_with(cold), run.load_metric("setup_keys_s")["params"]) == 0.0
    assert setup_hist_sum_s.read(window_with(cold), run.load_metric("setup_compile_s")["params"]) \
        == pytest.approx(30.7)  # a cold process compiled all of it anew


@pytest.mark.parametrize("hists", [
    {},
    {"serve.stage_ms.total": hist(90.0)},
    # the parent's listener: backend compiles alone. Its sum would pass for
    # `compiled anew` with the reads still inside it, so nothing is read
    {"xla.compile_ms.none": hist(300.0), "xla.compile_ms.g1_msm.call": hist(20100.0)},
])
def test_a_program_that_files_no_trace_leaves_nothing_to_read(hists, capsys):
    for metric in NEW:
        assert setup_hist_sum_s.read(window_with(hists), run.load_metric(metric)["params"]) is None
    assert capsys.readouterr().err == ""


# ---- the manifest's new entries ------------------------------------------------


def test_every_new_metric_has_its_file_its_reader_and_accepted_cells_that_report_setup_s():
    manifest = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert [m["name"] for m in manifest["per_layer"] if m["moves"] == "setup_s"] == NEW
    for name in NEW:
        spec = run.load_metric(name)
        assert spec["reducer"] == "setup_hist_sum_s"
        assert callable(importlib.import_module(f"benchmark.reducers.{spec['reducer']}").read)
        entry = entries[name]
        assert (entry["layer"], entry["unit"], entry["better"], entry["source"]) == (
            "set-up", "s", "lower", "program_span")
        assert entry["workloads"] == ([ATTS] if name == "setup_keys_s" else cells[:5])
        for cell in entry["workloads"]:
            assert "setup_s" in {m["name"] for m in run.load_cell(cell).end_to_end}


# ---- the CPU rehearsal ----------------------------------------------------------


REHEARSAL = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
from tests.benchmark_harness.tiny import drive
window, traffic, device = drive({cell!r}, seed=2**31 + 35, seconds=0.5)
line = run.result_line(window, True, run.compare(window, traffic), device)
print(json.dumps({{"line": line, "setup_s": window.setup_seconds}}))
"""


def test_the_state_root_cells_line_splits_its_set_up():
    # a process of the cell's own, as on the chip: this worker's registry holds
    # earlier tests' compiles, and they warmed its jit caches
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(root=ROOT, cell=STATE_ROOT)],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    line, setup_s = out["line"], out["setup_s"]
    assert line["correct"] and line["failed"] == 0
    metrics = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert set(XLA) | {"setup_unnamed_s"} <= set(metrics) and "setup_keys_s" not in metrics
    assert metrics["setup_trace_s"] > 0 and metrics["setup_lower_s"] > 0
    assert metrics["setup_compile_s"] > 0 and metrics["setup_cache_read_s"] == 0.0  # no cache on the CPU
    assert metrics["setup_unnamed_s"] > 0 and metrics["setup_compile_s"] >= 0  # nothing filed twice
    assert sum(metrics[name] for name in [*XLA, "setup_unnamed_s"]) == pytest.approx(setup_s, abs=1e-6)
    assert all(entry["unit"] == "s" for name, entry in line["metrics"].items() if name in NEW)
    # the reader's detail: the leg the seconds sat under
    assert "xla.trace_ms.state_root.launch" in proc.stderr
