"""Each traffic driver end to end on the CPU at a tiny size, through the
harness's own `drive`, `compare` and `result_line` (only the look for a chip
is skipped); each cell's control, which has to come out as not correct; and
the timed path broken underneath, which has to as well."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchmark import faults, run

from .tiny import ROOT, drive

STATE_ROOT, BLOBS, SLOTS = "state_root_2p20.recompute", "blob_block_6.verify", "slot_2p20.sync"


correct = run.is_correct


@pytest.fixture(scope="module")
def state_root_run():
    return drive(STATE_ROOT, seed=2**31 + 5, seconds=6.0, traced=True)  # 4 requests and more, also on a loaded host


@pytest.fixture(scope="module")
def blob_run():
    return drive(BLOBS, seed=2**31 + 6, seconds=3.0)


def test_state_root_driver_end_to_end(state_root_run):
    window, traffic, device = state_root_run
    line = run.result_line(window, False, run.compare(window, traffic), device)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == window.completed >= 4
    assert set(line["metrics"]) == {"verdict_ms", "setup_s"}
    assert line["metrics"]["verdict_ms"]["value"] == pytest.approx(
        window.seconds * 1e3 / window.completed)
    assert list(line)[-1] == "compared" and line["compared"]["roots_wrong"] == {"value": 0, "limit": 0}
    # no two consecutive requests hand over the same columns
    sets = [k for k, _ in traffic.answers]
    assert all(a != b for a, b in zip(sets, sets[1:])) and len(set(sets)) == 4
    # the traced line: what the histograms hold is read; with no device plane
    # in a CPU trace the device's metrics are left out, never written as 0
    traced = run.result_line(window, True, run.compare(window, traffic), device)
    assert {"serve_wait_ms", "host_prep_ms", "dispatch_ms", "window_compiles"} <= set(traced["metrics"])
    assert not {"kernel_ms.state_root", "state_root_roofline", "device_idle_pct"} & set(traced["metrics"])
    assert traced["metrics"]["window_compiles"]["value"] == 0 and "window_s" in traced["device"]
    json.dumps(traced)


def test_state_root_control_is_not_correct(state_root_run):
    window, traffic, _ = state_root_run
    compared = run.compare(window, traffic, control=True)
    assert compared["roots_wrong"][0] == window.completed and not correct(compared)


def test_blob_driver_end_to_end(blob_run):
    window, traffic, device = blob_run
    line = run.result_line(window, False, run.compare(window, traffic), device)
    assert line["correct"] and line["failed"] == 0 and window.completed >= 1
    assert set(line["metrics"]) == {"verdict_ms", "setup_s"}
    # every block of the tiny cell carries a wrong proof in each half of its
    # four-sidecar flush, and those two alone are refused
    for number, verdicts in traffic.answers:
        refused = tuple(i for i, v in enumerate(verdicts) if not v)
        assert refused == traffic.wrong_places(number) == (1, 3)


def test_blob_control_is_not_correct(blob_run):
    window, traffic, _ = blob_run
    compared = run.compare(window, traffic, control=True)
    assert compared["verdicts_wrong"][0] == 2 * window.completed and not correct(compared)


def test_slot_driver_end_to_end():
    window, traffic, device = drive(SLOTS, seed=3, seconds=60.0)
    line = run.result_line(window, False, run.compare(window, traffic), device)
    assert line["correct"] and line["failed"] == 0
    assert window.completed == 2  # the prepared slots are used up, the window closes
    assert line["compared"]["slot_fields_wrong"] == {"value": 0, "limit": 0}


# ---- the timed path broken underneath: `correct` has to come out false ------


@pytest.mark.parametrize("cell, fault, seconds", [
    (STATE_ROOT, "root_altered", 0.3),
    (STATE_ROOT, "root_stale", 0.3),
    (BLOBS, "first_half_unchecked", 1.0),
    (BLOBS, "second_half_unchecked", 1.0),
    (BLOBS, "verdict_altered", 1.0),
])
def test_a_fault_planted_in_the_program_is_not_correct(cell, fault, seconds):
    with faults.planted(fault):
        window, traffic, _ = drive(cell, seed=11, seconds=seconds)
    assert window.completed >= 1 and not correct(run.compare(window, traffic))


def test_the_bisecting_block_carries_a_wrong_proof_in_each_half_at_the_cells_own_size():
    """At six sidecars a block: one wrong proof in each half of the flush,
    never a half's first, on every seed; every other block all valid."""
    from benchmark.traffic import blob_block

    cell = run.load_cell(BLOBS)
    seen = set()
    for seed in range(2**31, 2**31 + 40):
        traffic = blob_block.Traffic(cell.config, cell.traffic["params"], seed)
        wrong = [n for n in range(30) if traffic.carries_wrong_proofs(n)]
        assert wrong == [traffic.invalid_first]
        first, second = traffic.wrong_places(wrong[0])
        assert first in (1, 2) and second in (4, 5)
        seen.add((first, second))
    assert len(seen) == 4  # the seeds walk through every pair of places


def test_fault_an_unanswered_request_is_not_correct(state_root_run):
    window, traffic, _ = state_root_run
    window.attempted += 1
    try:
        assert run.compare(window, traffic)["unanswered"] == (1, 0)
    finally:
        window.attempted -= 1


# ---- the command ------------------------------------------------------------


def test_the_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", STATE_ROOT,
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
