"""The yardstick's arithmetic on inputs small enough to count by hand: the
trace reduction on a hand-built trace, the histogram reader on two
snapshots, the needed-work functions at depth 3, and the two
plain references against the program's own host oracles."""

from __future__ import annotations

import os

import numpy as np
import pytest

from benchmark import needed, peaks, run, xplane
from benchmark.reducers import (
    device_idle_pct,
    hbm_roofline_pct,
    hist_mean_ms,
    trace_program_ms,
)

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def window():
    cell = run.Cell("c", 1, {"validators": 1 << 20}, {}, [], [])
    w = run.Window(cell, "TPU v5 lite", setup_seconds=1.0)
    w.trace = xplane.Trace.from_json(os.path.join(HERE, "small_trace.json"))
    return w


def test_busy_is_the_union_and_idle_its_rest(window):
    # programs at 0-1.5, 3.0-3.5 (two, back to back) and 3.6-3.7; the window is 4 s
    assert xplane.busy_seconds(window.trace) == pytest.approx(2.1)
    assert device_idle_pct.read(window, {}) == pytest.approx(47.5)
    assert xplane.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    # a plane where nothing ran is no device, and operations nest (a while
    # spans its body): neither changes the busy time
    xplane.add_device(window.trace, [], [])
    window.trace.ops[0].append(("%while.1 = (s32[]) while(...)", 0.0, 1.5))
    assert xplane.busy_seconds(window.trace) == pytest.approx(2.1)
    assert xplane.top_device_ops(window.trace)[0] == ["%while.1", 1.5]
    # without a line of programs the operations' own intervals stand in
    window.trace.modules[0] = []
    assert xplane.busy_seconds(window.trace) == pytest.approx(2.0)


def test_program_time_by_pattern_a_request(window):
    # jit_run twice, 1.75 s, over 2 requests; jit_running_sum must not match
    assert trace_program_ms.read(window, {"pattern": r"^jit_run(\(|$)"}) == pytest.approx(875.0)
    assert trace_program_ms.read(window, {"pattern": r"^jit_msm_many_kernel"}) == pytest.approx(125.0)
    # nothing to read is nothing, never 0
    assert trace_program_ms.read(window, {"pattern": "^jit_absent"}) is None
    window.trace = None
    assert trace_program_ms.read(window, {"pattern": "^jit_run"}) is None
    assert device_idle_pct.read(window, {}) is None


def test_a_pattern_that_matches_two_distinct_programs_is_an_error(window):
    """Two functions of the program are called `run`: where both are in a
    trace, `^jit_run` names no one kernel, and summing them is refused."""
    window.trace.modules[0].append(("jit_run(18)", 3.8, 0.1))
    with pytest.raises(xplane.TraceError, match="2 distinct programs"):
        trace_program_ms.read(window, {"pattern": r"^jit_run(\(|$)"})
    # a pattern that tells them apart reads the one it names
    assert trace_program_ms.read(window, {"pattern": r"^jit_run\(17\)"}) == pytest.approx(875.0)


def test_breakdown_names_the_gap_by_what_the_client_did(window):
    assert xplane.top_device_ops(window.trace)[:2] == [["fusion.1", 1.0], ["fusion.2", 1.0]]
    # the long gap, 1.5-3.0: 0.85 s of it the client waited, 0.05 s it submitted
    assert xplane.idle_gaps(window.trace) == [
        ["client.wait_verdict", pytest.approx(1.5)], ["client.between_requests", pytest.approx(0.1)]]


def test_roofline_share_divides_the_sourced_peak(window, monkeypatch):
    monkeypatch.setattr(run.Window, "metric", lambda self, name: 100.0)  # 100 ms a root
    least_s = needed.state_root_least_bytes(1 << 20) / 819e9
    got = hbm_roofline_pct.read(
        window, {"of": "kernel_ms.state_root", "bytes": "state_root_least_bytes", "size": "validators"}
    )
    assert got == pytest.approx(100 * least_s / 0.1) and 0 < got < 1
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")  # no borrowed row: unknown device = error


def test_histogram_reader_takes_exact_sums_between_two_snapshots(window):
    window.hist_before = {"serve.stage_ms.prep": {"sum": 10.0, "count": 2, "p50": 999.0}}
    window.hist_after = {
        "serve.stage_ms.prep": {"sum": 40.0, "count": 5, "p50": 999.0},
        "serve.stage_ms.queue": {"sum": 8.0, "count": 4},
        "serve.stage_ms.idle": {"sum": 0.0, "count": 0},
    }
    names = ["serve.stage_ms.prep", "serve.stage_ms.queue", "serve.stage_ms.idle", "absent"]
    assert hist_mean_ms.read(window, {"histograms": names}) == pytest.approx(30 / 3 + 8 / 4)
    assert hist_mean_ms.read(window, {"histograms": ["absent"]}) is None


def test_needed_work_at_depth_3_by_hand_and_not_what_the_program_executes():
    from eth_consensus_specs_tpu.ops.merkle import tree_real_hashes

    assert needed.tree_hashes(8) == 7 and needed.tree_hashes(24) == 12 + 6 + 3 + 2 + 1
    # 8 validators: 24 leaf hashes; registry 7 + 37 folds + mix-in; two u64
    # lists of 2 chunks (1 + 37 + 1 each); flags in 1 chunk (0 + 35 + 1);
    # three checkpoints; the 24-field top tree
    assert needed.state_root_needed_hashes(8) == 24 + (7 + 38) + 2 * (1 + 38) + 36 + 3 + 24
    assert needed.state_root_least_bytes(8) == 8 * (96 + 24 + 1) + 24 * 32 + 32
    # the program counts d * 2**(d-1) a tree since its level loop: not followed
    assert tree_real_hashes(3) == 12 != needed.tree_hashes(8)


def test_kzg_reference_agrees_with_the_program_and_its_setup():
    from benchmark.reference import kzg_ref
    from eth_consensus_specs_tpu.crypto import kzg

    rng = np.random.default_rng(7)
    sidecars = []
    for _ in range(2):
        raw = rng.integers(0, 256, (4096, 32), dtype=np.uint8)
        raw[:, 0] = 0
        sidecars.append((raw.tobytes(), *kzg_ref.commit_and_prove(raw.tobytes())))
    (blob, commitment, proof), (_, _, other_proof) = sidecars
    # the trapdoor is the setup's: tau * G1 is its second monomial point
    assert kzg_ref.g1_compress(kzg_ref.g1_mul(kzg_ref.G1_JAC, kzg_ref.TAU)) == bytes.fromhex(
        run.load_json(kzg.setup_path(4096))["g1_monomial"][1][2:]
    )
    assert kzg_ref.verify_blob(blob, commitment, proof) is True
    assert kzg.verify_blob_kzg_proof(blob, commitment, proof) is True
    assert kzg_ref.verify_blob(blob, commitment, other_proof) is False
    assert kzg.verify_blob_kzg_proof(blob, commitment, other_proof) is False
    assert kzg_ref.verify_blob(blob, b"\x00" * 48, proof) is False
    assert kzg_ref.accept_without_proof(blob, commitment, other_proof) is True


@pytest.mark.parametrize("n", [64, 100, 1000])
def test_state_root_reference_agrees_with_the_program_host_oracle(n):
    """Also at registries that fill no whole chunk (100 = 3.125 chunks of
    participation flags): a real registry is no power of two."""
    import jax

    import __graft_entry__ as graft
    from benchmark.reference import state_root_ref
    from eth_consensus_specs_tpu.ops.slot_pipeline import _root_bytes, slot_spec
    from eth_consensus_specs_tpu.ops.state_root import (
        post_epoch_state_root_host,
        synthetic_static,
    )

    cols, just = graft._example_altair_inputs(n)
    arrays, meta = synthetic_static(slot_spec(), n)
    columns = [np.asarray(c) for c in (cols.balance, cols.effective_balance, cols.inactivity_scores)]
    want = _root_bytes(post_epoch_state_root_host(
        arrays, meta, *columns, jax.tree_util.tree_map(np.asarray, just)
    ))
    static = {k: np.asarray(v) for k, v in arrays._asdict().items()}
    got = state_root_ref.state_root(static, *columns, {k: np.asarray(v) for k, v in just._asdict().items()})
    assert got == want
    assert tuple((i, f) for i, f in enumerate(state_root_ref.FIELDS)
                 if (i, f) in meta.dynamic_slots) == meta.dynamic_slots
    assert meta.top_depth == state_root_ref.TOP_DEPTH
