"""The metrics that read the program's legs (`waterfall.leg`): the two new
readers on inputs small enough to count by hand, the new entries of
BENCHMARK.json against their files, and both cells' traced lines on the CPU
rehearsal."""

from __future__ import annotations

import importlib
import json
import os
import re

import pytest

from benchmark import host_spans, run, xplane
from benchmark.reducers import hist_count_per_request, idle_named_pct

from .tiny import ROOT, drive

HERE = os.path.dirname(os.path.abspath(__file__))
STATE_ROOT, BLOBS = "state_root_2p20.recompute", "blob_block_6.verify"
BLOB_LEGS = {"kzg_brp_ms", "kzg_horner_ms", "kzg_rlc_fold_ms", "kzg_pairing_ms", "fr_fft_pack_ms",
             "fr_fft_call_ms", "fr_fft_unpack_ms", "g1_msm_pack_ms", "g1_msm_call_ms",
             "g1_msm_unpack_ms"}
STATE_ROOT_LEGS = {"state_root_launch_ms", "state_root_wait_ms"}
EVERYWHERE = {"dispatch_other_ms", "idle_named_pct"}
NEW = BLOB_LEGS | STATE_ROOT_LEGS | EVERYWHERE | {"fr_fft_call_compiles"}


def window_with(before: dict, after: dict, completed: int = 2) -> run.Window:
    w = run.Window(run.Cell("c", 1, {}, {}, [], []), "TPU v5 lite", setup_seconds=1.0)
    w.hist_before, w.hist_after, w.latencies_ms = before, after, [1.0] * completed
    return w


# ---- hist_count_per_request --------------------------------------------------


def test_count_per_request_reads_a_count_a_zero_or_nothing():
    params = {"histogram": "xla.compile_ms.fr_fft.call", "present": "serve.stage_ms.device.fr_fft.call"}
    leg_ran = {"serve.stage_ms.device.fr_fft.call": {"count": 18, "sum": 90.0}}
    leg_before = {"serve.stage_ms.device.fr_fft.call": {"count": 6, "sum": 30.0}}
    compiled = {"xla.compile_ms.fr_fft.call": {"count": 9, "sum": 700.0}}
    # six compile events in a window of two blocks, three before it
    w = window_with({**leg_before, "xla.compile_ms.fr_fft.call": {"count": 3, "sum": 200.0}},
                    {**leg_ran, **compiled})
    assert hist_count_per_request.read(w, params) == 3.0
    # the histogram first seen inside the window
    assert hist_count_per_request.read(window_with(leg_before, {**leg_ran, **compiled}), params) == 4.5
    # the leg ran and nothing compiled under it: 0.0, with or without the histogram
    assert hist_count_per_request.read(window_with(leg_before, leg_ran), params) == 0.0
    assert hist_count_per_request.read(window_with({**leg_before, **compiled}, {**leg_ran, **compiled}), params) == 0.0
    # the leg did not run (the parent commit, another cell): nothing to read
    assert hist_count_per_request.read(window_with({}, {}), params) is None
    assert hist_count_per_request.read(window_with(leg_ran, leg_ran), params) is None
    assert hist_count_per_request.read(window_with(leg_before, {**leg_ran, **compiled}, completed=0), params) is None


# ---- idle_named_pct ------------------------------------------------------------


@pytest.fixture()
def fixture():
    with open(os.path.join(HERE, "small_host_spans.json")) as f:
        return json.load(f)


@pytest.fixture()
def traced_window(fixture, monkeypatch):
    """A window whose trace holds the fixture's programs; the host planes'
    events come from the fixture in the file's place, through the pattern."""
    def from_fixture(trace_dir, pattern):
        assert trace_dir == host_spans.TRACE_DIR == os.path.join(ROOT, ".bench_trace")
        rx = re.compile(pattern)
        threads = [[tuple(r) for r in rows if rx.match(r[0])] for rows in fixture["threads"]]
        return [rows for rows in threads if rows]

    monkeypatch.setattr(host_spans, "read_host_spans", from_fixture)
    legs = ("fr_fft.unpack", "kzg.pairing", "state_root.launch", "state_root.wait", "other")
    w = window_with({}, {f"serve.stage_ms.device.{leg}": {"count": 1, "sum": 1.0} for leg in legs})
    w.trace = xplane.Trace(ops=[[]], modules=[[tuple(r) for r in fixture["modules"][0]]],
                           window_s=9.0, requests=2)
    return w


PARAMS = run.load_metric("idle_named_pct")["params"]


def test_innermost_pieces_are_self_times(fixture):
    pieces = host_spans.innermost([tuple(r) for r in fixture["threads"][0]])
    self_s: dict = {}
    for name, start, end in pieces:
        assert end > start
        self_s[name] = self_s.get(name, 0.0) + end - start
    assert all(a[2] <= b[1] for a, b in zip(pieces, pieces[1:]))  # no two overlap
    # serve.dispatch: 4.1 s less kzg.verify_many and kzg.pairing (2.4 + 0.45),
    # then 1.25 s less state_root.post_epoch (1.2)
    assert self_s["serve.dispatch"] == pytest.approx(1.25 + 0.05)
    assert self_s["kzg.verify_many"] == pytest.approx(2.4 - 1.2)
    assert self_s["fr_fft.unpack"] == pytest.approx(1.2)
    assert self_s["state_root.launch"] == pytest.approx(0.35 - 0.33)
    assert sum(self_s.values()) == pytest.approx(4.1 + 1.25)


def test_a_gap_goes_to_the_span_with_most_self_time_over_it(traced_window, capsys):
    threads = [host_spans.innermost(rows)
               for rows in host_spans.read_host_spans(host_spans.TRACE_DIR, PARAMS["spans"])]
    assert len(threads) == 2  # the client's thread holds no span of the program
    gaps = [(1.0, 2.0), (3.0, 4.0), (5.0, 6.0), (7.0, 8.0)]
    named = host_spans.name_gaps(gaps, threads, waits={"serve.batch_wait"})
    assert [span for span, _, _ in named] == ["fr_fft.unpack", "serve.dispatch", "serve.batch_wait", None]
    assert [s for _, s, _ in named] == pytest.approx([1.0] * 4)
    assert [self_s.get(span, 0.0) for span, _, self_s in named] == pytest.approx([1.0, 0.55, 0.5, 0.0])
    assert named[2][2] == pytest.approx({"serve.batch_wait": 0.5, "serve.prep": 0.1, "serve.dispatch": 0.03,
                                         "state_root.post_epoch": 0.02, "state_root.launch": 0.3})
    # without the rule for waiting spans the batch thread's wait, which spans
    # the whole first dispatch, would take the gap that no one leg fills
    assert host_spans.name_gaps(gaps, threads, waits=set())[1][0] == "serve.batch_wait"
    # two of the four idle seconds lie under a named piece of the program
    assert idle_named_pct.read(traced_window, PARAMS) == pytest.approx(50.0)
    err = [line for line in capsys.readouterr().err.splitlines() if line.startswith("idle gap")]
    assert len(err) == 4 and "fr_fft.unpack 1.0" in err[0] and "unnamed" not in err[0]
    assert "serve.batch_wait 0.5" in err[2] and "state_root.launch 0.3" in err[2] and "serve.prep 0.1" in err[2]
    assert sum("unnamed" in line for line in err) == 2
    assert any("no span of the program" in line for line in err)


def test_idle_named_reads_nothing_from_a_program_without_the_named_spans(traced_window, fixture):
    # the parent commit: serve.dispatch and kzg.verify_many alone, no leg histogram
    fixture["threads"] = [[r for r in fixture["threads"][0] if r[0] in ("serve.dispatch", "kzg.verify_many")]]
    traced_window.hist_after = {}
    assert idle_named_pct.read(traced_window, PARAMS) is None
    # no device plane (a CPU trace), no trace
    traced_window.trace.modules = []
    assert idle_named_pct.read(traced_window, PARAMS) is None
    traced_window.trace = None
    assert idle_named_pct.read(traced_window, PARAMS) is None


# ---- the manifest's new entries ------------------------------------------------


def test_every_new_metric_has_its_file_its_reader_and_cells_that_report_what_it_moves():
    manifest = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert NEW <= set(entries)
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == [
        m["name"] for m in manifest["per_layer"] if m["name"] in NEW]  # appended, nothing moved
    for name in NEW:
        spec = run.load_metric(name)
        assert callable(importlib.import_module(f"benchmark.reducers.{spec['reducer']}").read)
        assert entries[name]["moves"] == "verdict_ms"
    reported = lambda cell: {m["name"] for m in run.load_cell(cell).per_layer} & NEW  # noqa: E731
    assert reported(BLOBS) == BLOB_LEGS | EVERYWHERE | {"fr_fft_call_compiles"} and len(reported(BLOBS)) == 13
    assert reported(STATE_ROOT) == STATE_ROOT_LEGS | EVERYWHERE and len(reported(STATE_ROOT)) == 4
    for cell in (BLOBS, STATE_ROOT):
        assert "verdict_ms" in {m["name"] for m in run.load_cell(cell).end_to_end}


# ---- the CPU rehearsal: the traced lines -----------------------------------------


def per_layer_line(cell: str, seed: int, seconds: float, traced: bool) -> tuple[dict, run.Window]:
    window, traffic, device = drive(cell, seed=seed, seconds=seconds, traced=traced)
    return run.result_line(window, True, run.compare(window, traffic), device), window


def legs_tile_the_dispatch(metrics: dict, legs: set) -> None:
    split = sum(metrics[name]["value"] for name in legs | {"dispatch_other_ms"})
    assert split == pytest.approx(metrics["dispatch_ms"]["value"], rel=1e-6)


def test_the_blob_cells_traced_line_carries_every_leg():
    # the per-layer line of a window the profiler did not run over: on the CPU
    # every operation of a limb program is a host event, and a traced flush
    # writes gigabytes; what the histograms hold is read all the same
    line, window = per_layer_line(BLOBS, seed=2**31 + 7, seconds=1.0, traced=False)
    metrics = line["metrics"]
    assert line["correct"] and line["failed"] == 0
    assert BLOB_LEGS | {"dispatch_other_ms", "fr_fft_call_compiles"} <= set(metrics)
    assert "idle_named_pct" not in metrics
    assert all(metrics[name]["value"] > 0 for name in BLOB_LEGS)
    legs_tile_the_dispatch(metrics, BLOB_LEGS)
    # the program's listener and the benchmark's own log hear the same events
    assert metrics["fr_fft_call_compiles"]["value"] * window.completed <= window.compiles["compiles"]


def test_the_state_root_cells_traced_line_carries_its_legs(tmp_path, monkeypatch):
    # a trace directory of this test's own: test_rehearsal.py traces the same
    # cell, in another worker, and removes the checkout's before it starts
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(host_spans, "TRACE_DIR", run.TRACE_DIR)
    line, _ = per_layer_line(STATE_ROOT, seed=2**31 + 8, seconds=1.0, traced=True)
    metrics = line["metrics"]
    assert line["correct"] and line["failed"] == 0
    assert STATE_ROOT_LEGS | {"dispatch_other_ms"} <= set(metrics)
    assert not (BLOB_LEGS | {"fr_fft_call_compiles", "idle_named_pct"}) & set(metrics)  # no device plane
    legs_tile_the_dispatch(metrics, STATE_ROOT_LEGS)
    # the spans are on the profiler's clock: the trace's host planes hold them
    names = {name for rows in host_spans.read_host_spans(host_spans.TRACE_DIR, PARAMS["spans"])
             for name, _, _ in rows}
    assert {"serve.batch_wait", "serve.prep", "serve.dispatch", "state_root.post_epoch",
            "state_root.launch", "state_root.wait"} <= names
    assert not [name for name in names if name.startswith("client.")]
