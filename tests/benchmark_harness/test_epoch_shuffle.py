"""The committee cell on the CPU at a 4,096-lane bucket and a registry of
8,192: the same driver, files and harness as on the chip. The program's
answers compare as correct, the control and every planted fault as not."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import faults_shuffle, needed_shuffle, run
from benchmark.compile_log import CompileLog
from benchmark.reducers import needed_roofline_pct
from benchmark.reference import shuffle_ref
from benchmark.traffic import epoch_shuffle

CELL = "committees_2p20.shuffle"
POOL = 3
LEGS = ["shuffle_pack_ms", "shuffle_call_ms", "shuffle_unpack_ms", "dispatch_other_ms"]


def tiny_cell(**params) -> run.Cell:
    cell = run.load_cell(CELL)
    cell.config.update(validators=4096, registry=8192, warmup_keys=[["shuffle", 4096]])
    cell.traffic["params"].update(epoch_pool=POOL, first_active=4096 - 256 - 77, **params)
    return cell


def drive(seed: int, seconds: float = 1.5, **params):
    return run.drive(tiny_cell(**params), seed, seconds, False, "cpu", CompileLog().install(),
                     time.perf_counter())


@pytest.fixture(scope="module")
def driven():
    return drive(2147485901)


def test_the_window_cycles_the_pool_and_the_program_is_correct(driven):
    window, traffic, _ = driven
    requests = window.attempted
    assert requests > 2 * POOL and (window.completed, window.failed) == (requests, 0)
    served = [k for k, _ in traffic.answers]
    assert served == [traffic.order[i % POOL] for i in range(requests)]
    assert all(a != b for a, b in zip(served, served[1:]))
    # the client's own comparison of an answer with the copy it keeps is timed apart
    assert 0 < traffic.keep_seconds < window.seconds
    # one distinct answer an epoch, whatever the count of requests
    assert {k: len(v) for k, v in traffic.distinct.items()} == dict.fromkeys(range(POOL), 1)
    compared = run.compare(window, traffic)
    assert compared == {"lists_wrong": (0, 0), "unanswered": (0, 0)}
    assert run.is_correct(compared)
    # the answers are the spec's own per-index form at EVERY position here
    for k, (active, seed) in enumerate(traffic.epochs):
        want = [active[shuffle_ref.compute_shuffled_index(i, len(active), seed)]
                for i in range(len(active))]
        assert traffic.distinct[k][0].tolist() == want


def test_the_control_reads_every_answer_wrong(driven):
    window, traffic, _ = driven
    compared = run.compare(window, traffic, control=True)
    assert compared["lists_wrong"] == (window.attempted, 0) and not run.is_correct(compared)


def test_every_epoch_has_its_own_seed_set_and_count_under_the_bucket(driven):
    _, traffic, _ = driven
    counts = [len(active) for active, _ in traffic.epochs]
    assert counts[0] == 4096 - 256 - 77
    assert all(0 < abs(b - a) <= 16 for a, b in zip(counts, counts[1:]))
    assert all(2048 < n < 4096 and n % 256 for n in counts) and len(set(counts)) == POOL
    assert len({seed for _, seed in traffic.epochs}) == POOL
    for active, _ in traffic.epochs:
        assert active.dtype == np.int32  # the request the issue names: 4 bytes an index
        assert (np.diff(active) > 0).all() and active[-1] < 8192
        assert not np.array_equal(active, np.arange(len(active)))
    cell = tiny_cell()
    again = type(traffic)(cell.config, cell.traffic["params"], traffic.seed)
    again.make_inputs()
    assert all(np.array_equal(a, b) and s == t
               for (a, s), (b, t) in zip(again.epochs, traffic.epochs))
    other = type(traffic)(cell.config, cell.traffic["params"], traffic.seed + 1)
    other.make_inputs()
    assert other.epochs[0][1] != traffic.epochs[0][1]


def test_the_cells_own_counts_sit_under_2p20_off_a_whole_chunk():
    """At the cell's own size, counts alone (no list is made): every seed
    walks from 1,044,403 in non-zero steps of at most 16, never onto a
    multiple of 256, and stays under the 2**20 bucket."""
    cell = run.load_cell(CELL)
    assert cell.config["warmup_keys"] == [["shuffle", 1 << 20]]
    params = cell.traffic["params"]
    assert (params["epoch_pool"], params["first_active"], params["churn_per_epoch"]) == (
        8, (1 << 20) - 4096 - 77, 16)
    assert "requests_prepared" not in params  # time-windowed, as the state-root cell


@pytest.mark.parametrize("fault", sorted(faults_shuffle.FAULTS))
def test_a_planted_fault_is_not_correct(fault):
    with faults_shuffle.planted(fault, warm=POOL):
        window, traffic, _ = drive(2147485902, timeout_s=0.3)
    compared = run.compare(window, traffic)
    assert not run.is_correct(compared)
    if fault == "never_answers":
        assert compared["unanswered"][0] == window.attempted >= 1
    else:
        assert compared["lists_wrong"] == (window.completed, 0) and window.completed >= 1


def test_an_answer_of_another_length_is_wrong(driven):
    _, traffic, _ = driven
    want = shuffle_ref.shuffled_list(*traffic.epochs[0])
    assert not traffic.is_wrong(0, traffic.distinct[0][0], want)
    assert traffic.is_wrong(0, traffic.distinct[0][0][:-1], want)
    assert traffic.is_wrong(0, np.append(traffic.distinct[0][0], 0), want)


def test_the_legs_sum_to_the_dispatch_and_the_counters_read(driven):
    window, _, _ = driven
    values = {name: window.metric(name) for name in LEGS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert sum(values.values()) == pytest.approx(window.metric("dispatch_ms"), rel=1e-6)
    # three different counts passed through ONE compiled program
    assert window.metric("shuffle_call_compiles") == 0.0
    assert window.metric("window_compiles") == 0.0
    # no trace on the CPU: the device metrics have nothing to read
    for name in ("kernel_ms.shuffle", "shuffle_roofline", "shuffle_idle_named_pct"):
        assert window.metric(name) is None
    line = run.result_line(window, True, {"unanswered": (0, 0)}, {})
    assert set(LEGS) | {"shuffle_call_compiles"} <= set(line["metrics"])


def test_a_program_without_the_verb_fails_at_once():
    cell = tiny_cell()
    traffic = epoch_shuffle.Traffic(cell.config, cell.traffic["params"], 1)
    with pytest.raises(RuntimeError, match="submit_committees"):
        traffic.setup(SimpleNamespace())
    assert not hasattr(traffic, "epochs")  # before any input was made


def test_the_roofline_counts_what_the_algorithm_needs_from_shapes():
    # the indices read once, the list written once, the seed and 90 pivots
    assert needed_shuffle.shuffle_least_bytes(1 << 20) == 8 * (1 << 20) + 32 + 360 == 8389000
    config = run.load_cell(CELL).config
    window = SimpleNamespace(cell=SimpleNamespace(config=config), device_kind="TPU v5 lite",
                             metric=lambda name: 40.0)
    share = needed_roofline_pct.read(window, run.load_metric("shuffle_roofline")["params"])
    assert share == pytest.approx(100 * (8389000 / 819e9) / 0.040)
    window.metric = lambda name: None
    assert needed_roofline_pct.read(window, run.load_metric("shuffle_roofline")["params"]) is None
