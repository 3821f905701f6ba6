"""obs subsystem: registry (spans/counters/JSONL), gates, watchdog.

The tier-1 acceptance story: spans nest and aggregate, counters are
thread-safe totals, the JSONL sink round-trips, roofline verdicts attach
to any timing that declares work_bytes, and the watchdog records both
the clean path (divergences == 0 on CPU, where device == host by
construction) and the mismatch path (a corrupted device result MUST land
in watchdog.divergences — the metric round 4 was missing)."""

import json
import threading

import numpy as np
import pytest

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.obs import gates, watchdog
from eth_consensus_specs_tpu.obs.registry import Registry


# ------------------------------------------------------------------ registry --


def test_counter_aggregation_thread_safe():
    reg = Registry()

    def bump():
        for _ in range(1000):
            reg.count("t.x", 1)
            reg.count("t.bytes", 64)

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counters["t.x"] == 8000
    assert reg.counters["t.bytes"] == 8 * 64000


def test_span_nesting_and_aggregation():
    reg = Registry()
    with reg.span("outer"):
        with reg.span("inner") as sp:
            sp.result = np.arange(4)
        with reg.span("inner"):
            pass
    snap_spans = reg.snapshot()["spans"]
    assert snap_spans["outer"]["count"] == 1
    assert snap_spans["inner"]["count"] == 2
    assert snap_spans["inner"]["parent"] == "outer"
    assert snap_spans["inner"]["depth"] == 1
    assert snap_spans["outer"]["depth"] == 0
    assert snap_spans["inner"]["total_s"] >= snap_spans["inner"]["min_s"] > 0


def test_span_roofline_verdict_attached():
    reg = Registry()
    with reg.span("k.fast", work_bytes=10**15):  # exabyte/s-class: impossible
        pass
    agg = reg.snapshot()["spans"]["k.fast"]
    assert agg["roofline_ok"] is False
    assert agg["roofline_violations"] == 1
    assert agg["implied_gbps"] > gates.roofline_bytes_s() / 1e9
    # a later clean timing cannot launder the aggregate verdict
    with reg.span("k.fast", work_bytes=96):
        pass
    agg = reg.snapshot()["spans"]["k.fast"]
    assert agg["roofline_ok"] is False and agg["roofline_violations"] == 1


def test_jsonl_round_trip(tmp_path):
    reg = Registry()
    sink = str(tmp_path / "events.jsonl")
    reg.configure_jsonl(sink)
    reg.count("x", 1)  # counters don't emit events
    with reg.span("roundtrip", work_bytes=96):
        pass
    reg.emit({"kind": "custom", "payload": 7})
    lines = [json.loads(ln) for ln in open(sink)]
    kinds = [ln["kind"] for ln in lines]
    assert "span" in kinds and "custom" in kinds
    span_ev = next(ln for ln in lines if ln["kind"] == "span")
    assert span_ev["name"] == "roundtrip"
    assert "implied_gbps" in span_ev and "roofline_ok" in span_ev
    reg.configure_jsonl(None)


def test_obs_disabled_is_noop(monkeypatch):
    from eth_consensus_specs_tpu.obs import registry as registry_mod

    monkeypatch.setenv("ETH_SPECS_OBS", "0")
    assert registry_mod.refresh_enabled() is False
    try:
        reg = Registry()
        reg.count("never", 1)
        with reg.span("never") as sp:
            sp.result = 3
        assert reg.counters == {} and reg.spans == {}
    finally:
        monkeypatch.setenv("ETH_SPECS_OBS", "1")
        assert registry_mod.refresh_enabled() is True


# --------------------------------------------------------------------- gates --


def test_gates_digest_bytes_and_ndarray_agree():
    arr = np.arange(16, dtype=np.uint32)
    assert gates.digest(arr) == gates.digest(arr.tobytes())
    assert len(gates.digest(b"x")) == 32


def test_gates_roofline_verdict():
    ok = gates.roofline_verdict(1e9, 1.0)
    assert ok["roofline_ok"] and ok["implied_gbps"] == 1.0
    bad = gates.roofline_verdict(1e15, 0.001)
    assert not bad["roofline_ok"]


def test_gates_roofline_is_keyed_by_device_kind_and_unknown_is_an_error():
    # v5e: 819 GB/s HBM a chip (Google Cloud documentation, "TPU v5e")
    assert gates.roofline_bytes_s("TPU v5 lite") == 2 * 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        gates.roofline_bytes_s("TPU v9")


# ------------------------------------------------------------------ watchdog --


@pytest.fixture(autouse=True)
def _fresh_watchdog_counters(monkeypatch):
    """Isolated registry + reset call counters: the mismatch-path tests
    below record divergences ON PURPOSE, and those must never leak into
    the process registry — the run-level obs_report.json (and the CI
    smoke on it) asserts the real kernels diverged zero times."""
    from eth_consensus_specs_tpu.obs import registry as registry_mod

    watchdog.reset_for_tests()
    monkeypatch.setattr(registry_mod, "_REGISTRY", Registry())
    yield
    watchdog.reset_for_tests()


def _wd_counters():
    c = obs.snapshot()["counters"]
    return (
        c.get("watchdog.checks", 0),
        c.get("watchdog.divergences", 0),
    )


def test_watchdog_sha256_clean_and_mismatch_paths():
    rng = np.random.default_rng(3)
    words = rng.integers(0, 2**32, size=(8, 16), dtype=np.uint64).astype(np.uint32)
    from eth_consensus_specs_tpu.ops.sha256 import sha256_64B_batch_np

    digests8 = (
        sha256_64B_batch_np(words.astype(">u4").view(np.uint8).reshape(8, 64))
        .view(">u4")
        .astype(np.uint32)
        .reshape(8, 8)
    )
    checks0, div0 = _wd_counters()
    assert watchdog.check_sha256_slice(words, digests8)
    checks1, div1 = _wd_counters()
    assert checks1 == checks0 + 1 and div1 == div0

    corrupted = digests8.copy()
    corrupted[0, 0] ^= 1  # the device "did" the wrong work
    assert not watchdog.check_sha256_slice(words, corrupted)
    checks2, div2 = _wd_counters()
    assert checks2 == checks1 + 1
    assert div2 == div1 + 1  # the mismatch is a first-class metric


def test_watchdog_merkle_full_replay_and_mismatch():
    rng = np.random.default_rng(4)
    words = rng.integers(0, 2**32, size=(16, 8), dtype=np.uint64).astype(np.uint32)
    root = watchdog.host_tree_root_words(words)
    assert watchdog.check_merkle_root(words, 4, root)
    _, div0 = _wd_counters()
    assert not watchdog.check_merkle_root(words, 4, b"\x00" * 32)
    _, div1 = _wd_counters()
    assert div1 == div0 + 1


def test_watchdog_shuffle_spec_loop_matches_device():
    from eth_consensus_specs_tpu.ops.shuffle import shuffle_permutation

    n, seed, rounds = 201, b"\x07" * 32, 10
    perm = shuffle_permutation(n, seed, rounds)
    assert watchdog.check_shuffle_slice(perm, n, seed, rounds)
    bad = perm.copy()
    bad[0] = (bad[0] + 1) % n
    _, div0 = _wd_counters()
    assert not watchdog.check_shuffle_slice(bad, n, seed, rounds)
    _, div1 = _wd_counters()
    assert div1 == div0 + 1


def test_watchdog_sampling_rate_env(monkeypatch):
    monkeypatch.setenv("ETH_SPECS_OBS_WATCHDOG", "0")
    assert not watchdog.should_check("never_kernel")
    monkeypatch.setenv("ETH_SPECS_OBS_WATCHDOG", "1")
    assert watchdog.should_check("always_kernel")
    assert watchdog.should_check("always_kernel")
    monkeypatch.setenv("ETH_SPECS_OBS_WATCHDOG", "0.5")
    hits = [watchdog.should_check("half_kernel") for _ in range(4)]
    assert hits == [True, False, True, False]


def test_watchdog_first_call_always_checked(monkeypatch):
    monkeypatch.setenv("ETH_SPECS_OBS_WATCHDOG", "0.01")
    assert watchdog.should_check("rare_kernel")  # call 1 of interval 100
    assert not watchdog.should_check("rare_kernel")


# ------------------------------------------------------ end-to-end kernel obs --


def test_kernel_counters_fixture_sees_device_tree(kernel_counters, monkeypatch):
    monkeypatch.setenv("ETH_SPECS_OBS_WATCHDOG", "1")
    from eth_consensus_specs_tpu.ops.merkle import merkleize_subtree_device

    rng = np.random.default_rng(5)
    chunks = rng.integers(0, 256, size=(32, 32), dtype=np.uint8)
    root = merkleize_subtree_device(chunks, 5)
    delta = kernel_counters()
    assert delta["merkle.trees"] == 1
    assert delta["merkle.leaf_chunks"] == 32
    assert delta.get("watchdog.merkle.checks", 0) >= 1
    assert delta.get("watchdog.merkle.divergences", 0) == 0
    # the watchdog's zero-XLA host oracle agrees with the device root
    words = chunks.view(">u4").astype(np.uint32).reshape(32, 8)
    assert watchdog.host_tree_root_words(words) == root
    spans = obs.snapshot()["spans"]
    assert "merkle.subtree_root" in spans
    assert "roofline_ok" in spans["merkle.subtree_root"]
