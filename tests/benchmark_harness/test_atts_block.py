"""The attestation cell on the CPU at 64 validators and four aggregates a
block: the same driver, files and harness as on the chip. The program's
answers compare as correct, the control and every planted fault as not."""

from __future__ import annotations

import hashlib
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import faults_bls, needed_bls, run
from benchmark.compile_log import CompileLog
from benchmark.reducers import needed_roofline_pct
from benchmark.reference import bls_ref
from benchmark.traffic import atts_block

CELL = "block_atts_128.verify"
BLOCKS = 3
EVERY = 2  # a bisecting block every so many: block 0, EVERY and 2 x EVERY of 2 x EVERY + 1


def tiny_cell() -> run.Cell:
    manifest = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = run.load_cell(CELL)
    cell.config.update(validators=64, committee_size=4, aggregates_per_block=4)
    cell.config["serve_config"]["max_batch"] = 4
    cell.config["warmup_keys"] = [["bls_keysum", 4, 4, 64]]
    cell.traffic["params"].update(blocks_prepared=BLOCKS, invalid_first=1)
    assert cell.per_layer == run.reported(manifest, "per_layer", CELL)
    return cell


def drive(seed: int):
    return run.drive(tiny_cell(), seed, 60.0, False, "cpu", CompileLog().install(),
                     time.perf_counter())


@pytest.fixture(scope="module")
def driven():
    return drive(2147483901)


def test_the_window_is_the_prepared_blocks_and_the_program_is_correct(driven):
    window, traffic, _ = driven
    assert (window.attempted, window.completed, window.failed) == (BLOCKS, BLOCKS, 0)
    assert [len(v) for _, v in traffic.answers] == [4] * BLOCKS
    # block 1 carries a wrong aggregate in each half, refused alone
    first, second = traffic.wrong_places(1)
    assert [v for _, v in traffic.answers] == [
        (True,) * 4, tuple(i not in (first, second) for i in range(4)), (True,) * 4]
    compared = run.compare(window, traffic)
    assert compared == {"verdicts_wrong": (0, 0), "unanswered": (0, 0)}
    assert run.is_correct(compared)


def test_the_control_accepts_both_wrong_aggregates_and_is_not_correct(driven):
    window, traffic, _ = driven
    compared = run.compare(window, traffic, control=True)
    assert compared["verdicts_wrong"] == (2, 0) and not run.is_correct(compared)


def test_the_same_seed_makes_the_same_blocks_and_another_seed_others(driven):
    _, traffic, _ = driven
    again = type(traffic)(tiny_cell().config, tiny_cell().traffic["params"], traffic.seed)
    again.make_inputs()
    same = lambda a, b: all(  # noqa: E731
        (x[0] == y[0]).all() and x[1:] == y[1:] for x, y in zip(a, b))
    assert all(same(again.block(n), traffic.block(n)) for n in range(BLOCKS))
    other = type(traffic)(tiny_cell().config, tiny_cell().traffic["params"], traffic.seed + 1)
    other.make_inputs()
    assert other.block(0)[0][1:] != traffic.block(0)[0][1:]
    # half a block's committees are the block before's, the registry cycles
    assert same(traffic.block(3)[:2], traffic.block(2)[2:])
    assert traffic.slots == 8 and same(traffic.block(8), traffic.block(0))


@pytest.fixture(scope="module")
def driven_every():
    cell = tiny_cell()
    cell.traffic["params"].update(blocks_prepared=2 * EVERY + 1, invalid_first=0, invalid_every=EVERY)
    return run.drive(cell, 2147483905, 60.0, False, "cpu", CompileLog().install(),
                     time.perf_counter())


def test_invalid_every_repeats_the_bisecting_block_and_each_wrong_aggregate_is_refused_alone(
        driven_every):
    window, traffic, _ = driven_every
    blocks = 2 * EVERY + 1
    assert (window.attempted, window.completed, window.failed) == (blocks, blocks, 0)
    bisecting = [n for n in range(blocks) if traffic.carries_wrong_aggregates(n)]
    assert bisecting == [0, EVERY, 2 * EVERY]
    for number, verdicts in traffic.answers:
        wrong = traffic.wrong_places(number) if number in bisecting else ()
        assert verdicts == tuple(i not in wrong for i in range(4))
        for i, (signers, message, signature) in enumerate(traffic.block(number)):
            keys = [traffic.known.pubkeys[v] for v in signers]
            assert bls_ref.fast_aggregate_verify(traffic.known, keys, message, signature) == (i not in wrong)
    assert run.compare(window, traffic) == {"verdicts_wrong": (0, 0), "unanswered": (0, 0)}
    # the control accepts all six wrong aggregates
    compared = run.compare(window, traffic, control=True)
    assert compared["verdicts_wrong"] == (6, 0) and not run.is_correct(compared)


def digest(traffic, blocks: int) -> str:
    h = hashlib.sha256()
    for warmup in (False, True):
        for number in range(blocks):
            for signers, message, signature in traffic.block(number, warmup=warmup):
                h.update(signers.astype("<i4").tobytes() + message + signature)
    return h.hexdigest()


def test_without_invalid_every_the_blocks_are_those_of_one_bisecting_block():
    cell = tiny_cell()
    params = dict(cell.traffic["params"], blocks_prepared=10)
    params.pop("invalid_every", None)
    alone = atts_block.Traffic(cell.config, params, 2147483904)
    alone.make_inputs()
    # blocks 0-9 and their warm-up draws as the driver made them before `invalid_every`
    assert digest(alone, 10) == "439eb6b4e1544edda9d8204fbfbc60ac109500c94eb45e174c239de51cbfbeb7"
    assert [n for n in range(10) if alone.carries_wrong_aggregates(n)] == [1]
    # with it, the blocks between the bisecting ones are the same blocks
    every = atts_block.Traffic(cell.config, dict(params, invalid_every=4), 2147483904)
    every.make_inputs()
    for number in range(10):
        same = all(np.array_equal(x[0], y[0]) and x[1:] == y[1:]
                   for x, y in zip(every.block(number), alone.block(number)))
        assert same == (number not in (5, 9))


@pytest.mark.parametrize("fault", sorted(faults_bls.FAULTS))
def test_a_planted_fault_is_not_correct(fault):
    with faults_bls.planted(fault):
        window, traffic, _ = drive(2147483902)
    compared = run.compare(window, traffic)
    assert compared["verdicts_wrong"][0] >= 1 and not run.is_correct(compared)


def test_the_legs_sum_to_the_dispatch_and_the_counters_read(driven):
    window, _, _ = driven
    legs = ["bls_keys_ms", "bls_g1_sum_call_ms", "bls_g1_sum_unpack_ms", "bls_h2c_ms",
            "bls_g2_fold_ms", "bls_pairing_ms", "dispatch_other_ms"]
    values = {name: window.metric(name) for name in legs}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert sum(values.values()) == pytest.approx(window.metric("dispatch_ms"), rel=1e-6)
    # two valid blocks of one check, and 1 + 2 + 4 checks to bisect four
    assert window.metric("bls_rlc_checks") == pytest.approx((1 + 7 + 1) / BLOCKS)
    # the registry was resident before the window: no key decompressed in it
    assert window.metric("bls_key_decode_flushes") == 0.0
    assert window.metric("window_compiles") == 0.0
    # no trace on the CPU: the device metrics have nothing to read
    assert window.metric("kernel_ms.bls_g1_sum") is None
    assert window.metric("bls_g1_sum_roofline") is None


def test_one_aggregate_a_block_is_data_on_the_same_driver():
    """`sync_aggregate_512.verify`, kept for later: a block of ONE aggregate."""
    cell = tiny_cell()
    cell.config.update(aggregates_per_block=1, committee_size=8, warmup_keys=[])
    cell.config["serve_config"]["max_batch"] = 1
    window, traffic, _ = run.drive(cell, 2147483903, 60.0, False, "cpu", CompileLog().install(),
                                   time.perf_counter())
    assert [v for _, v in traffic.answers] == [(True,), (False,), (True,)]
    assert run.is_correct(run.compare(window, traffic))
    assert run.compare(window, traffic, control=True)["verdicts_wrong"] == (1, 0)


def test_the_roofline_counts_what_the_algorithm_needs_from_shapes():
    assert needed_bls.committee_sums_least_bytes(128, 512) == 128 * 512 * 96 + 128 * 96
    cell = SimpleNamespace(config={"aggregates_per_block": 128, "committee_size": 512})
    window = SimpleNamespace(cell=cell, device_kind="TPU v5 lite", metric=lambda name: 50.0)
    params = run.load_metric("bls_g1_sum_roofline")["params"]
    share = needed_roofline_pct.read(window, params)
    assert share == pytest.approx(100 * (6303744 / 819e9) / 0.050)
    window.metric = lambda name: None
    assert needed_roofline_pct.read(window, params) is None
