"""The data column cell on the CPU at 4 sidecars of 2 blobs a block: the
same driver, files and harness as on the chip. The program's answers compare
as correct, the control and every planted fault as not."""

from __future__ import annotations

import time
from types import SimpleNamespace

import pytest

from benchmark import faults_das, needed_das, run
from benchmark.compile_log import CompileLog
from benchmark.reducers import needed_roofline_pct
from benchmark.traffic import column_block

CELL = "peerdas_block_21.verify"
BLOCKS = 3
LEGS = ["das_fold_ms", "das_fft_pack_ms", "das_fft_call_ms", "das_fft_unpack_ms",
        "das_interp_fold_ms", "das_msm_pack_ms", "das_msm_call_ms", "das_msm_unpack_ms",
        "das_check_ms", "dispatch_other_ms"]


def tiny_cell(columns: int = 4) -> run.Cell:
    cell = run.load_cell(CELL)
    cell.config.update(columns_per_block=columns, blobs_per_block=2, cells_per_block=2 * columns)
    cell.config["serve_config"]["max_batch"] = columns
    # the two buckets of such a block: 2 cells a sidecar, two items a sidecar x 2 lanes
    cell.config["warmup_keys"] = [["fr_fft", 2 * columns, 64], ["das_msm", 2 * columns, 2]]
    cell.traffic["params"].update(blob_pool=3, blocks_prepared=BLOCKS, invalid_first=1)
    return cell


def drive(seed: int, columns: int = 4):
    return run.drive(tiny_cell(columns), seed, 120.0, False, "cpu", CompileLog().install(),
                     time.perf_counter())


@pytest.fixture(scope="module")
def driven():
    return drive(2147484901)


def test_the_window_is_the_prepared_blocks_and_the_program_is_correct(driven):
    window, traffic, _ = driven
    assert (window.attempted, window.completed, window.failed) == (BLOCKS, BLOCKS, 0)
    # block 1 carries a wrong proof in one sidecar of each half, refused alone
    wrong = [at for at, _ in traffic.wrong_places(1)]
    assert len(wrong) == 2 and wrong[0] == 1 and wrong[1] == 3  # never a half's first
    assert [v for _, v in traffic.answers] == [
        (True,) * 4, tuple(i not in wrong for i in range(4)), (True,) * 4]
    compared = run.compare(window, traffic)
    assert compared == {"verdicts_wrong": (0, 0), "unanswered": (0, 0)}
    assert run.is_correct(compared)


def test_the_control_accepts_both_wrong_sidecars_and_is_not_correct(driven):
    window, traffic, _ = driven
    compared = run.compare(window, traffic, control=True)
    assert compared["verdicts_wrong"] == (2, 0) and not run.is_correct(compared)


def test_the_same_seed_makes_the_same_blocks_and_another_seed_others(driven):
    _, traffic, _ = driven
    cell = tiny_cell()
    again = type(traffic)(cell.config, cell.traffic["params"], traffic.seed)
    again.make_inputs()
    assert all(again.block(n) == traffic.block(n) for n in range(BLOCKS))
    other = type(traffic)(cell.config, cell.traffic["params"], traffic.seed + 1)
    other.make_inputs()
    assert other.block(0)[0][1:] != traffic.block(0)[0][1:]
    block = traffic.block(0)
    # all of a block's sidecars carry its commitments; a column once; the warm-up's draw is another
    assert len({s[2] for s in block}) == 1 and len({s[0] for s in block}) == 4
    assert traffic.block(1, warmup=True) != traffic.block(1)
    # the wrong proof is a valid proof of another cell: the same blob's, the next column's
    (at, row), _ = traffic.wrong_places(1)
    col = traffic.block(1)[at][0]
    blobs = {commitment: proofs for commitment, _, proofs in traffic.pool}
    assert traffic.block(1)[at][3][row] == blobs[traffic.block(1)[at][2][row]][(col + 1) % 128]


@pytest.mark.parametrize("fault", sorted(faults_das.FAULTS))
def test_a_planted_fault_is_not_correct(fault):
    with faults_das.planted(fault):
        window, traffic, _ = drive(2147484902)
    compared = run.compare(window, traffic)
    assert compared["verdicts_wrong"][0] >= 1 and not run.is_correct(compared)


def test_the_legs_sum_to_the_dispatch_and_the_counters_read(driven):
    window, _, _ = driven
    values = {name: window.metric(name) for name in LEGS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert sum(values.values()) == pytest.approx(window.metric("dispatch_ms"), rel=1e-6)
    # two valid blocks of one check, and 1 + 2 + 4 checks to bisect four sidecars;
    # ONE execution of the multi-MSM a block, the bisecting one included
    assert window.metric("das_rlc_checks") == pytest.approx((1 + 7 + 1) / BLOCKS)
    assert window.metric("das_msm_calls") == 1.0
    assert window.metric("das_fft_call_compiles") == 0.0
    assert window.metric("window_compiles") == 0.0
    # no trace on the CPU: the device metrics have nothing to read
    for name in ("kernel_ms.das_msm", "kernel_ms.das_fft", "das_msm_roofline",
                 "das_fft_roofline", "das_idle_named_pct"):
        assert window.metric(name) is None
    # every per-layer metric the cell lists has its file and its reader
    line = run.result_line(window, True, {"unanswered": (0, 0)}, {})
    assert set(LEGS) | {"das_rlc_checks", "das_msm_calls", "das_fft_call_compiles"} <= set(line["metrics"])


def test_eight_sidecars_a_block_is_data_on_the_same_driver():
    """`peerdas_custody_8.verify`, kept for later: a full node's 8 custody
    columns of a block, the first 8 of the seed's order."""
    window, traffic, _ = drive(2147484903, columns=8)
    assert [len(v) for _, v in traffic.answers] == [8] * BLOCKS
    wrong = [at for at, _ in traffic.wrong_places(1)]
    assert 1 <= wrong[0] < 4 <= wrong[1] - 1 < 7
    assert traffic.answers[1][1] == tuple(i not in wrong for i in range(8))
    assert run.is_correct(run.compare(window, traffic))
    assert run.compare(window, traffic, control=True)["verdicts_wrong"] == (2, 0)
    assert window.metric("das_msm_calls") == 1.0
    assert window.metric("das_rlc_checks") == pytest.approx((1 + 11 + 1) / BLOCKS)


def test_a_program_without_the_verb_fails_at_once():
    cell = tiny_cell()
    traffic = column_block.Traffic(cell.config, cell.traffic["params"], 1)
    with pytest.raises(RuntimeError, match="submit_column_verify"):
        traffic.setup(SimpleNamespace())
    assert not hasattr(traffic, "pool")  # before any input was made


def test_the_rooflines_count_what_the_algorithm_needs_from_shapes():
    # two sums a sidecar; a proof read as 96 affine bytes with its 32-byte scalar, a point out a sum
    assert needed_das.proof_sums_least_bytes(128, 21) == 256 * (21 * 128 + 96) == 712704
    # 2,688 cells of 64 field elements, 32 bytes in and 32 out
    assert needed_das.cell_interpolation_least_bytes(2688) == 2688 * 64 * 64 == 11010048
    config = run.load_cell(CELL).config
    window = SimpleNamespace(cell=SimpleNamespace(config=config), device_kind="TPU v5 lite",
                             metric=lambda name: 2000.0)
    share = needed_roofline_pct.read(window, run.load_metric("das_msm_roofline")["params"])
    assert share == pytest.approx(100 * (712704 / 819e9) / 2.0)
    window.metric = lambda name: 10.0
    share = needed_roofline_pct.read(window, run.load_metric("das_fft_roofline")["params"])
    assert share == pytest.approx(100 * (11010048 / 819e9) / 0.010)
    window.metric = lambda name: None
    assert needed_roofline_pct.read(window, run.load_metric("das_msm_roofline")["params"]) is None
