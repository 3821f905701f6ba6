"""Shuffle identity: the whole-permutation kernel must agree with the
per-index spec form everywhere, and be a true permutation."""

import numpy as np
import pytest

from benchmark.compile_log import CompileLog
from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.forks import get_spec
from eth_consensus_specs_tpu.obs import xprof
from eth_consensus_specs_tpu.ops import shuffle
from eth_consensus_specs_tpu.ops.shuffle import shuffle_permutation
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.serve.config import ServeConfig
from eth_consensus_specs_tpu.serve.service import VerifyService


@pytest.mark.parametrize("n", [1, 2, 7, 64, 257, 1000])
def test_permutation_matches_spec_form(n):
    spec = get_spec("phase0", "minimal")
    seed = bytes(range(32))
    perm = shuffle_permutation(n, seed, spec.SHUFFLE_ROUND_COUNT)
    for i in range(n):
        assert int(perm[i]) == spec.compute_shuffled_index(i, n, seed)


def test_is_permutation():
    seed = b"\xaa" * 32
    perm = shuffle_permutation(5000, seed, 90)
    assert sorted(perm.tolist()) == list(range(5000))


def test_seed_sensitivity():
    a = shuffle_permutation(256, b"\x01" * 32, 90)
    b = shuffle_permutation(256, b"\x02" * 32, 90)
    assert a.tolist() != b.tolist()


@pytest.mark.parametrize("n", [1, 255, 256, 257, 1000, 4096])
def test_device_permutation_bit_equal(n):
    """shuffle_permutation_device == host whole-permutation form ==
    compute_shuffled_index (via the host test above), incl. chunk-boundary
    sizes. 90 mainnet rounds."""
    import numpy as np

    from eth_consensus_specs_tpu.ops.shuffle import shuffle_permutation_device

    seed = b"\x5a" * 32
    host = shuffle_permutation(n, seed, 90)
    dev = np.asarray(shuffle_permutation_device(n, seed, 90))
    assert (host == dev).all()


# ------------------------------------ the program: the count a traced number --


def _spec_list(active, seed, rounds=90):
    """The per-index spec form at every position (the watchdog's own
    copy of the loop, which shares nothing with either whole-list form)."""
    from eth_consensus_specs_tpu.obs.watchdog import _spec_shuffled_index

    n = len(active)
    return [int(active[_spec_shuffled_index(i, n, seed, rounds)]) for i in range(n)]


def _epoch(n: int, tag: int = 0):
    rng = np.random.default_rng([n, tag, 0x5AFF])
    return np.sort(rng.choice(1 << 21, n, replace=False)), rng.bytes(32)


def _compiles() -> int:
    hists = obs.snapshot()["histograms"]
    return sum(v["count"] for k, v in hists.items() if k.startswith("xla.compile_ms."))


def test_one_compiled_program_serves_every_count_under_its_bucket():
    """n = 255, 256, 257, 1000 and 4096 under the 4,096 bucket: ONE
    executable, counted by the program's compile listener and by the
    benchmark's log; at a static count each n compiled its own."""
    xprof.install_compile_listener()
    log = CompileLog().install()
    active, seed = _epoch(3000)
    shuffle.shuffled_indices_device(active, seed, 90, lanes=4096)  # the one compile
    loaded = shuffle.shuffle_rounds_kernel._cache_size()
    mark, before = log.mark(), _compiles()
    for n in (255, 256, 257, 1000, 4096):
        active, seed = _epoch(n)
        got = shuffle.shuffled_indices_device(active, seed, 90, lanes=4096)
        assert got.dtype == np.int32 and got.shape == (n,)
        assert (got == active[shuffle_permutation(n, seed, 90)]).all()
    assert shuffle.shuffle_rounds_kernel._cache_size() == loaded
    assert _compiles() == before
    assert CompileLog.since(mark, log.mark())["compiles"] == 0


@pytest.mark.parametrize("n, lanes", [(1, 1), (2, 2), (7, 8), (255, 256), (1000, 1024), (1000, 4096)])
def test_the_program_is_the_per_index_spec_form_on_indices_that_are_not_arange(n, lanes):
    active, seed = _epoch(n, 1)
    assert not np.array_equal(active, np.arange(n)) or n == 1
    got = shuffle.shuffled_indices_device(active, seed, 90, lanes=lanes)
    assert got.tolist() == _spec_list(active, seed)


def test_lanes_past_the_count_never_move_and_are_never_read():
    """The padded list's tail comes back as it went in, and what it holds
    changes nothing below the count."""
    active, seed = _epoch(777, 2)
    pivots = shuffle._pivots(seed, 777, 90)
    words = np.frombuffer(seed, ">u4").astype(np.uint32)
    outs = []
    for fill in (0, 123456789):
        padded = np.full(1024, fill, np.int32)
        padded[:777] = active
        outs.append(np.asarray(shuffle.shuffle_rounds_kernel(words, pivots, np.int32(777), padded)))
        assert (outs[-1][777:] == fill).all()
    assert (outs[0][:777] == outs[1][:777]).all()
    assert outs[0][:777].tolist() == _spec_list(active, seed)


def test_fewer_rounds_compile_their_own_program_and_agree_with_the_host():
    active, seed = _epoch(300, 3)
    got = shuffle.shuffled_indices_device(active, seed, 10)
    assert (got == active[shuffle_permutation(300, seed, 10)]).all()
    assert shuffle.shuffle_permutation_device(0, seed, 90).shape == (0,)
    for lanes in (256, 768):  # under the count; not a power of two
        with pytest.raises(ValueError):
            shuffle.shuffled_indices_device(active, seed, 90, lanes=lanes)


def test_counters_count_live_lanes_and_hashes_not_the_bucket():
    active, seed = _epoch(1000, 4)
    was = obs.snapshot()["counters"]
    shuffle.shuffled_indices_device(active, seed, 90, lanes=4096)
    now = obs.snapshot()["counters"]
    delta = {k: now[k] - was.get(k, 0) for k in
             ("shuffle.permutations", "shuffle.lanes", "shuffle.decision_hashes")}
    assert delta == {"shuffle.permutations": 1, "shuffle.lanes": 1000,
                     "shuffle.decision_hashes": 90 * 4}


# ------------------------------------------------------------------ the verb --

LEGS = ("shuffle.pack", "shuffle.call", "shuffle.unpack")


def _leg_counts() -> dict:
    hists = obs.snapshot()["histograms"]
    return {leg: hists.get(f"serve.stage_ms.device.{leg}", {"count": 0})["count"] for leg in LEGS}


def test_the_verb_resolves_to_the_spec_form_on_both_routes():
    """An uncompiled bucket takes the host route and a precompiled one the
    device route: the same list, and the leg histograms say which."""
    active, seed = _epoch(1000, 5)
    want = _spec_list(active, seed)
    assert buckets.shuffle_key(1000) == ("shuffle", 1024)
    assert buckets.shuffle_key(1 << 20) == buckets.shuffle_key((1 << 20) - 4173) == (
        "shuffle", 1 << 20)
    buckets.reset_for_tests()
    with VerifyService(ServeConfig(max_wait_ms=1.0, mesh_chips=1), name="shuffle") as svc:
        before, was = _leg_counts(), obs.snapshot()["counters"]
        host = svc.submit_committees(active, seed).result(timeout=300)
        assert _leg_counts() == before and not buckets.is_compiled("shuffle", 1024)
        assert svc.precompile([("shuffle", 1024)]) == 1 and buckets.is_compiled("shuffle", 1024)
        before = _leg_counts()
        device = svc.submit_committees(active.astype(np.uint32), seed).result(timeout=300)
        # several requests in one flush run one execution each
        shorter = _epoch(999, 6)
        futs = [svc.submit_committees(*shorter), svc.submit_committees(active, seed)]
        answers = [f.result(timeout=300) for f in futs]
        assert _leg_counts() == {leg: before[leg] + 3 for leg in LEGS}
        counters = obs.snapshot()["counters"]
        assert counters["serve.requests.shuffle"] - was.get("serve.requests.shuffle", 0) == 4
        assert counters.get("serve.degraded_items", 0) == was.get("serve.degraded_items", 0)
    for got in (host, device, answers[1]):
        assert got.dtype == np.int32 and got.tolist() == want
    assert answers[0].tolist() == _spec_list(*shorter)


def test_the_degraded_flush_answers_from_the_host_form():
    from eth_consensus_specs_tpu import fault

    active, seed = _epoch(300, 7)
    with VerifyService(ServeConfig(max_wait_ms=1.0, mesh_chips=1), name="shuffle") as svc:
        with fault.injected("serve.dispatch:raise:times=inf"):
            got = svc.submit_committees(active, seed).result(timeout=300)
    assert got.tolist() == _spec_list(active, seed)


@pytest.mark.parametrize("active, seed", [
    (np.zeros((2, 2), np.int64), bytes(32)),  # not one-dimensional
    (np.zeros(4, np.float64), bytes(32)),  # not integers
    (np.zeros(0, np.int64), bytes(32)),  # empty
    ([1, 2, 3], bytes(32)),  # not an array
    (np.array([1, 1 << 31]), bytes(32)),  # past 2**31
    (np.array([-1, 5]), bytes(32)),  # negative
    (np.arange(4), bytes(31)),  # a short seed
])
def test_the_verb_refuses_another_shape_or_dtype(active, seed):
    with VerifyService(ServeConfig(mesh_chips=1), name="shuffle") as svc:
        with pytest.raises(ValueError):
            svc.submit_committees(active, seed)


def test_the_watchdog_samples_the_served_list_against_the_spec_loop():
    from eth_consensus_specs_tpu.obs import watchdog

    active, seed = _epoch(500, 8)
    good = active[shuffle_permutation(500, seed, 90)]
    assert watchdog.check_shuffle_slice(good, 500, seed, 90, active=active)
    bad = good.copy()
    bad[[0, 499]] = bad[[499, 0]]
    assert not watchdog.check_shuffle_slice(bad, 500, seed, 90, active=active)
