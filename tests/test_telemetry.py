"""Continuous-telemetry plane: series ring mechanics, anomaly detectors
on synthetic series (including a pinned zero-false-positive budget on
clean noise), windowed SLO burn rate, and the known-answer canary
scheduler's parity/exclusion contracts.

Everything here is synthetic and in-process — no replicas, no device
compiles (the one real-service test uses the bls canary, whose CPU path
is the host verifier). The detector tests ARE the documentation of each
detector's firing horizon: if a threshold changes, the pinned horizons
here must change with it.
"""

from __future__ import annotations

import concurrent.futures

import numpy as np
import pytest

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.obs import anomaly, slo, tsdb
from eth_consensus_specs_tpu.obs import canary as canary_mod
from eth_consensus_specs_tpu.obs.delta import DeltaShipper
from eth_consensus_specs_tpu.obs.histogram import Histogram

CFG = anomaly.AnomalyConfig()  # the documented defaults, not env state


def wait_hist(values) -> dict:
    h = Histogram()
    for v in values:
        h.record(float(v))
    return h.snapshot()


def mk_sample(t, wait=None, rate=None, events=(), counters=None, dt=1.0):
    """One synthetic telemetry window (1s wide by default)."""
    counters = dict(counters or {})
    rates = {k: v / dt for k, v in counters.items()}
    if rate is not None:
        rates["frontdoor.requests"] = rate
        counters["frontdoor.requests"] = rate * dt
    hists = {}
    if wait is not None:
        hists["serve.wait_ms"] = wait_hist(wait)
        hists["frontdoor.e2e_ms"] = wait_hist(wait)
    return tsdb.Sample(t=t, dt=dt, counters=counters, rates=rates,
                       hists=hists, events=list(events))


def feed(det, samples, ring=None):
    """Run a detector over samples; returns (fires, fire_indices)."""
    ring = ring or tsdb.SeriesRing(64)
    fires, idxs = [], []
    for i, s in enumerate(samples):
        ring.append(s)
        found = det.step(s, ring)
        fires.extend(found)
        idxs.extend([i] * len(found))
    return fires, idxs


# ------------------------------------------------------------- series ring --


def test_series_ring_bounded_and_ordered():
    ring = tsdb.SeriesRing(8)
    for i in range(13):
        ring.append(mk_sample(float(i), counters={"x": i}))
    assert len(ring) == 8
    assert ring.capacity == 8
    assert ring.samples()[0].t == 5.0  # oldest five evicted
    assert ring.span_s() == 7.0
    assert [s.t for s in ring.last(3)] == [10.0, 11.0, 12.0]


def test_sample_from_delta_rates_and_events():
    delta = {
        "counters": {"serve.requests": 10},
        "gauges": {"g": {"last": 3.0, "max": 5.0}},
        "histograms": {"serve.wait_ms": wait_hist([1.0, 2.0])},
        "flight": [{"kind": "frontdoor.replica_lost", "replica": 1}],
    }
    s = tsdb.sample_from_delta(delta, t=10.0, dt=2.0)
    assert s.rates["serve.requests"] == pytest.approx(5.0)
    assert s.hist_count("serve.wait_ms") == 2
    assert s.events[0]["replica"] == 1
    assert s.quantile("serve.wait_ms", 0.5) is not None
    assert s.quantile("missing", 0.99) is None


def test_gauge_series_carries_level_forward():
    ring = tsdb.SeriesRing(8)
    s0 = mk_sample(0.0)
    s0.gauges["canary.pass_rate"] = {"last": 1.0, "max": 1.0}
    ring.append(s0)
    ring.append(mk_sample(1.0))  # gauge unchanged: delta ships nothing
    s2 = mk_sample(2.0)
    s2.gauges["canary.pass_rate"] = {"last": 0.5, "max": 1.0}
    ring.append(s2)
    series = ring.gauge_series("canary.pass_rate")
    assert [v for _, v in series] == [1.0, 1.0, 0.5]


def test_quantile_series_skips_empty_windows():
    ring = tsdb.SeriesRing(8)
    ring.append(mk_sample(0.0, wait=[10.0]))
    ring.append(mk_sample(1.0))  # quiet window: no latency, not zero
    ring.append(mk_sample(2.0, wait=[20.0]))
    series = ring.quantile_series("serve.wait_ms", 0.99)
    assert [t for t, _ in series] == [0.0, 2.0]


def test_sampler_owns_cursor_and_counts():
    ship = DeltaShipper()
    ship.delta()
    sampler = tsdb.Sampler(capacity=16)
    obs.count("tsdb_test.marker", 1)
    s = sampler.sample(t=100.0)
    assert s.counters.get("tsdb_test.marker") == 1
    # the marker is in no catalog: take it out of the process-wide
    # registry again, or whichever file this worker runs next that
    # validates an exposition (tests/test_obs_export.py) refuses it
    obs.get_registry().counters.pop("tsdb_test.marker", None)
    # the sampler's own tsdb.samples bump lands in the NEXT window, and
    # a separate consumer's cursor still sees it (no window stealing)
    assert ship.delta()["counters"].get("tsdb.samples") == 1


# ----------------------------------------------------- structural detectors --


def test_dead_replica_fires_with_attribution():
    det = anomaly.DeadReplica(CFG)
    ev = {"kind": "frontdoor.replica_lost", "replica": 2, "exitcode": -9}
    fires, idxs = feed(det, [mk_sample(0.0), mk_sample(1.0, events=[ev])])
    assert len(fires) == 1 and idxs == [1]
    a = fires[0]
    assert a.replica == 2 and a.stage == "recovery"
    assert a.severity == "page" and a.windows == 1  # same-window horizon


def test_probe_stall_needs_consecutive_failures():
    det = anomaly.ProbeStall(CFG)
    fail = {"kind": "frontdoor.probe_failed", "replica": 1}
    # a success between failures resets the streak
    fires, _ = feed(det, [
        mk_sample(0.0, events=[fail]), mk_sample(1.0),
        mk_sample(2.0, events=[fail]),
    ])
    assert fires == []
    fires, idxs = feed(anomaly.ProbeStall(CFG), [
        mk_sample(0.0, events=[fail]), mk_sample(1.0, events=[fail]),
    ])
    assert len(fires) == 1 and idxs == [CFG.confirm - 1]
    assert fires[0].replica == 1 and fires[0].stage == "wire"


def test_completion_stall_fires_at_horizon_and_compiles_reset_it():
    det = anomaly.CompletionStall(CFG, "frontdoor.requests", "frontdoor.e2e_ms")
    samples = [mk_sample(0.0, rate=5.0)]
    samples += [mk_sample(float(i)) for i in range(1, CFG.stall_windows + 1)]
    fires, idxs = feed(det, samples)
    assert len(fires) == 1
    assert idxs == [CFG.stall_windows - 1]  # documented horizon, exactly
    # a cold-compile wall is not a stall: the compile delta resets it
    det = anomaly.CompletionStall(CFG, "frontdoor.requests", "frontdoor.e2e_ms")
    samples = [mk_sample(0.0, rate=5.0)]
    samples += [mk_sample(float(i)) for i in range(1, CFG.stall_windows - 1)]
    samples.append(mk_sample(99.0, counters={"serve.compiles": 1}))
    samples += [mk_sample(100.0 + i) for i in range(CFG.stall_windows - 1)]
    fires, _ = feed(det, samples)
    assert fires == []


# ---------------------------------------------------- statistical detectors --


def test_latency_step_fires_within_confirm_windows():
    rng = np.random.default_rng(7)
    det = anomaly.LatencyStep(CFG, "serve.wait_ms")
    base = [mk_sample(float(i), wait=rng.uniform(8, 12, 16))
            for i in range(CFG.warmup + 5)]
    stepped = [mk_sample(100.0 + i, wait=rng.uniform(95, 110, 16))
               for i in range(CFG.confirm + 1)]
    fires, idxs = feed(det, base + stepped)
    assert len(fires) == 1
    # documented horizon: within `confirm` windows of the step
    assert idxs[0] < len(base) + CFG.confirm
    assert fires[0].detector == "latency_step"


def test_latency_drift_fires_within_documented_horizon():
    rng = np.random.default_rng(8)
    det = anomaly.LatencyDrift(CFG, "serve.wait_ms")
    base = [mk_sample(float(i), wait=rng.uniform(9, 11, 16))
            for i in range(CFG.warmup)]
    # 8%/window exponential creep: crosses drift_ratio (3x) in
    # log(3)/log(1.08) ~ 14 windows; the EWMA lags a few more
    drift = [mk_sample(50.0 + i, wait=[10.0 * (1.08 ** i)] * 16)
             for i in range(40)]
    fires, idxs = feed(det, base + drift)
    assert fires, "drift never detected"
    horizon = idxs[0] - len(base)
    assert 14 <= horizon <= 25, f"drift horizon {horizon} outside documented band"


def test_rate_spike_and_stall():
    det = anomaly.RateSpike(CFG, "frontdoor.requests")
    base = [mk_sample(float(i), rate=100.0) for i in range(CFG.warmup + 3)]
    spike = [mk_sample(50.0 + i, rate=1500.0) for i in range(CFG.confirm)]
    fires, _ = feed(det, base + spike)
    assert len(fires) == 1 and fires[0].detector == "rate_spike"

    det = anomaly.RateStall(CFG, "frontdoor.requests")
    stall = [mk_sample(50.0 + i, rate=2.0) for i in range(CFG.confirm)]
    fires, _ = feed(det, base + stall)
    assert len(fires) == 1 and fires[0].detector == "rate_stall"
    # full idleness (rate 0) is NOT a stall — quiet fleets are healthy
    det = anomaly.RateStall(CFG, "frontdoor.requests")
    idle = [mk_sample(50.0 + i, rate=0.0) for i in range(20)]
    fires, _ = feed(det, base + idle)
    assert fires == []


def test_clean_noise_fires_nothing_fp_budget_zero():
    """The pinned false-positive budget: 500 windows of healthy jittery
    traffic must produce ZERO fires across the entire detector set."""
    rng = np.random.default_rng(20260807)
    slo.reset_windows_for_tests()
    dets = anomaly.default_detectors(CFG, "frontdoor", anomaly.ALL)
    ring = tsdb.SeriesRing(64)
    fired = []
    for i in range(500):
        s = mk_sample(float(i), wait=rng.uniform(8, 14, 24),
                      rate=float(rng.uniform(80, 120)))
        ring.append(s)
        for det in dets:
            fired.extend(det.step(s, ring))
    assert fired == [], f"false positives on clean noise: {fired}"


def test_engine_refractory_suppresses_repeat_fires():
    reg_before = obs.snapshot()["counters"].get("anomaly.fires", 0)
    eng = anomaly.Engine(CFG, detectors=[anomaly.DeadReplica(CFG)],
                         source="frontdoor", capture=False)
    ev = {"kind": "frontdoor.replica_lost", "replica": 0, "exitcode": -9}
    ring = tsdb.SeriesRing(16)
    ring.append(mk_sample(0.0, events=[ev]))
    assert len(eng.step(ring)) == 1
    # same replica again inside the refractory window: suppressed
    ring.append(mk_sample(1.0, events=[ev]))
    assert eng.step(ring) == []
    # a DIFFERENT replica is a different key: fires
    ev2 = {"kind": "frontdoor.replica_lost", "replica": 1, "exitcode": -9}
    ring.append(mk_sample(2.0, events=[ev2]))
    assert len(eng.step(ring)) == 1
    assert eng.fire_counts() == {"dead_replica": 2}
    assert obs.snapshot()["counters"].get("anomaly.fires", 0) == reg_before + 2
    rep = eng.report()
    assert rep["total"] == 2
    assert {f["replica"] for f in rep["fired"]} == {0, 1}


# ----------------------------------------------------------- slo burn rate --


def test_burn_rate_windowed():
    import time as _time

    slo.reset_windows_for_tests()
    assert slo.burn_rate(window_s=60.0) is None
    slo.note_window(True)  # a single live window is its own burn rate
    one = slo.burn_rate(window_s=60.0)
    assert one["windows"] == 1 and one["burn_rate"] == pytest.approx(1.0)
    slo.reset_windows_for_tests()
    now = _time.monotonic()
    slo.note_window(True, t=now - 120.0)  # ancient: outside the cap
    slo.note_window(True, t=now - 1.0)
    slo.note_window(False, t=now)
    capped = slo.burn_rate(window_s=60.0)
    assert capped["windows"] == 2 and capped["breached"] == 1
    assert capped["burn_rate"] == pytest.approx(0.5)
    assert capped["window_s"] == 60.0
    slo.reset_windows_for_tests()


def test_burn_rate_counters_path_unchanged():
    snap = {"counters": {"slo.windows": 10, "slo.windows_breached": 3}}
    overall = slo.burn_rate(snap)
    assert overall["windows"] == 10 and overall["breached"] == 3
    assert overall["burn_rate"] == pytest.approx(0.3)
    assert slo.burn_rate({"counters": {}}) is None


# ------------------------------------------------------------------ canary --


class FakeClient:
    """Resolves every canary instantly with a configurable result."""

    def __init__(self, result="correct"):
        self.mode = result
        self.calls = 0

    def submit_hash_tree_root(self, chunks, canary=False):
        assert canary is True
        self.calls += 1
        fut = concurrent.futures.Future()
        if self.mode == "correct":
            from eth_consensus_specs_tpu.obs.watchdog import host_tree_root_words
            from eth_consensus_specs_tpu.ops.merkle import _chunks_to_words

            fut.set_result(
                host_tree_root_words(_chunks_to_words(chunks, chunks.shape[0])))
        elif self.mode == "wrong":
            fut.set_result(b"\x00" * 32)
        elif self.mode == "error":
            fut.set_exception(RuntimeError("shed"))
        else:  # hang
            pass
        return fut


def test_canary_pass_and_pass_rate():
    sched = canary_mod.CanaryScheduler(FakeClient(), interval_s=100.0,
                                       shapes=("htr",))
    sched._next_t = 0.0
    sched.pump(now=1.0)  # send
    sched.pump(now=1.1)  # reap (next send not due for 100s)
    st = sched.stats()
    assert st["sent"] == 1 and st["ok"] == 1
    assert st["parity_failures"] == 0 and st["pass_rate"] == 1.0


def test_canary_parity_failure_counts_and_pages():
    before = obs.snapshot()["counters"].get("canary.parity_failures", 0)
    sched = canary_mod.CanaryScheduler(FakeClient("wrong"), interval_s=0.0,
                                       shapes=("htr",))
    sched._next_t = 0.0
    sched.pump(now=1.0)
    sched.pump(now=1.1)
    st = sched.stats()
    assert st["parity_failures"] == 1 and st["ok"] == 0
    assert st["pass_rate"] == 0.0
    after = obs.snapshot()["counters"].get("canary.parity_failures", 0)
    assert after == before + 1


def test_canary_error_and_timeout_are_degraded_not_parity():
    sched = canary_mod.CanaryScheduler(FakeClient("error"), interval_s=0.0,
                                       shapes=("htr",))
    sched._next_t = 0.0
    sched.pump(now=1.0)
    sched.pump(now=1.1)
    assert sched.stats()["errors"] == 1
    assert sched.stats()["parity_failures"] == 0

    sched = canary_mod.CanaryScheduler(FakeClient("hang"), interval_s=0.0,
                                       timeout_s=5.0, shapes=("htr",))
    sched._next_t = 0.0
    sched.pump(now=1.0)
    sched.pump(now=2.0)  # still pending, inside timeout
    assert sched.stats()["errors"] == 0
    sched.pump(now=7.1)  # past timeout
    assert sched.stats()["errors"] == 1
    assert sched.stats()["parity_failures"] == 0


def test_canary_at_most_one_in_flight():
    client = FakeClient("hang")
    sched = canary_mod.CanaryScheduler(client, interval_s=0.0, shapes=("htr",))
    sched._next_t = 0.0
    for i in range(5):
        sched.pump(now=1.0 + i * 0.01)
    assert client.calls == 1  # the hang blocks further sends


def test_canary_warm_keys_are_fixed_shapes():
    keys = canary_mod.warm_keys(("bls", "htr", "agg"))
    assert ("merkle_many", 1, 6) in keys
    assert ("bls_msm", 1, 4) in keys
    assert ("g2_agg", 1, 4) in keys
    kzg = canary_mod.warm_keys(("kzg",))
    assert ("kzg", 4) in kzg
    assert ("fr_fft", 1, 4096) in kzg


def test_canary_excluded_from_serving_metrics():
    """The exclusion contract end to end on a real in-process service:
    a canary never lands in serve.requests / serve.wait_ms / admission,
    and lives in the canary.* family instead. bls only — its CPU path
    is the host verifier, so this compiles nothing."""
    from eth_consensus_specs_tpu.serve.config import ServeConfig
    from eth_consensus_specs_tpu.serve.service import VerifyService

    svc = VerifyService(ServeConfig(max_batch=4, max_wait_ms=2))
    try:
        ship = DeltaShipper()
        ship.delta()  # baseline
        payload, expected = canary_mod._BUILDERS["bls"]()
        got = svc.submit_bls_aggregate(*payload, canary=True).result(timeout=30)
        assert canary_mod.bits(got) == canary_mod.bits(expected)
        assert svc.admission.depth() == 0  # exempt: never admitted
        d = ship.delta()
        assert d["counters"].get("canary.requests", 0) == 1
        assert d["counters"].get("serve.requests", 0) == 0
        hists = d.get("histograms", {})
        assert hists.get("serve.wait_ms", {}).get("count", 0) == 0
        assert hists.get("canary.wait_ms", {}).get("count", 0) == 1
    finally:
        svc.close()
