"""perf_track ingestion contract for the aggregation bench section.

The tracker once burned this repo by comparing in the wrong frame; the
agg section adds a new hazard class — rate metrics whose names end in
``_per_s`` would match the lower-is-better ``_s`` suffix rule and gate
throughput IMPROVEMENTS as regressions. These tests pin the direction
table and the section ingestion so a rename can't silently flip it."""

from __future__ import annotations

import importlib.util
import json
import os

_SPEC = importlib.util.spec_from_file_location(
    "perf_track",
    os.path.join(os.path.dirname(__file__), "..", "scripts", "perf_track.py"),
)
perf_track = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(perf_track)


def test_per_s_rates_are_higher_is_better():
    assert not perf_track._lower_is_better("attestations_agg_per_s")
    assert not perf_track._lower_is_better("agg_signatures_agg_per_s")
    assert not perf_track._lower_is_better("r2x8_rps")
    assert not perf_track._lower_is_better("incremental_root_speedup")
    # walls/latency/bytes still compare lower-is-better
    assert perf_track._lower_is_better("agg_slot_wall_s")
    assert perf_track._lower_is_better("resident_epoch_plus_root_ms")
    assert perf_track._lower_is_better("peak_bytes")


def _write_round(tmp_path, n, parsed):
    path = tmp_path / f"BENCH_r{n:02d}.json"
    path.write_text(json.dumps({"rc": 0, "parsed": parsed}))
    return path


def test_load_rounds_ingests_agg_section(tmp_path):
    _write_round(
        tmp_path, 1,
        {
            "metric": "attestations_agg_per_s", "value": 900.0,
            "platform": "cpu",
            "agg": {
                "attestations_agg_per_s": 900.0,
                "signatures_agg_per_s": 210000.0,
                "slot_wall_s": 4.5,
            },
        },
    )
    rounds = perf_track.load_rounds(str(tmp_path))
    assert len(rounds) == 1 and rounds[0]["status"] == "ok"
    m = rounds[0]["metrics"]
    # the primary keeps its name; section values prefix agg_ unless
    # they already carry it (no agg_agg_ stutter)
    assert m["attestations_agg_per_s"] == 900.0
    assert m["agg_signatures_agg_per_s"] == 210000.0
    assert m["agg_slot_wall_s"] == 4.5


def test_agg_rate_drop_gates_and_rise_does_not(tmp_path):
    base = {
        "metric": "attestations_agg_per_s",
        "platform": "cpu",
    }
    _write_round(tmp_path, 1, {**base, "value": 1000.0,
                               "agg": {"attestations_agg_per_s": 1000.0}})
    _write_round(tmp_path, 2, {**base, "value": 500.0,
                               "agg": {"attestations_agg_per_s": 500.0}})
    _write_round(tmp_path, 3, {**base, "value": 2000.0,
                               "agg": {"attestations_agg_per_s": 2000.0}})
    rounds = perf_track.load_rounds(str(tmp_path))
    regressions, _ = perf_track.compare(rounds, threshold=0.30, strict=False)
    flagged = {(r["round"], r["metric"]) for r in regressions}
    # the 1000 -> 500 drop gates; the 500 -> 2000 RISE must not (the
    # direction a bare "_s" suffix rule would have inverted)
    assert (2, "attestations_agg_per_s") in flagged
    assert not any(r == 3 for r, _ in flagged)


def test_load_rounds_ingests_das_section(tmp_path):
    _write_round(
        tmp_path, 1,
        {
            "metric": "blobs_per_s", "value": 40.0, "platform": "cpu",
            "das": {
                "blobs_per_s": 40.0,
                "ffts_per_s": 40.0,
                "flush_wall_s": 0.2,
                "correctness_coupled": True,
            },
        },
    )
    rounds = perf_track.load_rounds(str(tmp_path))
    assert len(rounds) == 1 and rounds[0]["status"] == "ok"
    m = rounds[0]["metrics"]
    assert m["das_blobs_per_s"] == 40.0
    assert m["das_ffts_per_s"] == 40.0
    assert m["das_flush_wall_s"] == 0.2
    # the parity flag is a gate marker, not a metric (bool is an int
    # subclass — the ingest must not let it ride the timeline)
    assert "das_correctness_coupled" not in m
    # direction table: blob rates are higher-is-better, walls lower
    assert not perf_track._lower_is_better("das_blobs_per_s")
    assert not perf_track._lower_is_better("das_ffts_per_s")
    assert perf_track._lower_is_better("das_flush_wall_s")


def test_quarantined_das_lkg_can_only_be_replaced_by_parity_coupled_run():
    """The re-earn-never-grandfather rule: copying the quarantined das
    numbers back into the usable LKG sections WITHOUT the
    correctness_coupled flag fails the tracker; a parity-coupled
    re-earned section passes; quarantined-only stays fine."""
    quarantined = {"quarantined": ["das"], "sections": {}, "present": True}
    assert perf_track.reearn_violations(quarantined) == []
    grandfathered = {
        "present": True,
        "quarantined": ["das"],
        "sections": {"das": {"das_ffts_per_sec": 621.1}},
    }
    assert perf_track.reearn_violations(grandfathered) == ["das"]
    # das is re-earn-only even if the quarantine note itself is deleted
    scrubbed = {
        "present": True,
        "quarantined": [],
        "sections": {"das": {"blobs_per_s": 40.0}},
    }
    assert perf_track.reearn_violations(scrubbed) == ["das"]
    reearned = {
        "present": True,
        "quarantined": ["das"],
        "sections": {"das": {"blobs_per_s": 40.0, "correctness_coupled": True}},
    }
    assert perf_track.reearn_violations(reearned) == []
    # bench.py's _store_lkg form counts too: verified must be the
    # literal True, NOT the "same-backend" CPU-lane string it writes
    # when coupling did not actually run against a host recompute
    bench_form = {
        "present": True,
        "quarantined": ["tree"],
        "sections": {"tree": {"hashes_per_sec": 3e9, "verified": True}},
    }
    assert perf_track.reearn_violations(bench_form) == []
    cpu_lane = {
        "present": True,
        "quarantined": ["epoch"],
        "sections": {"epoch": {
            "fused_epoch_ms": 5.0,
            "verified": "same-backend (CPU lane; coupling applies to accelerator runs)",
        }},
    }
    assert perf_track.reearn_violations(cpu_lane) == ["epoch"]
    # a truthy-but-not-True flag is not a parity proof
    sloppy = {
        "present": True,
        "quarantined": [],
        "sections": {"das": {"correctness_coupled": 1.0}},
    }
    assert perf_track.reearn_violations(sloppy) == ["das"]
