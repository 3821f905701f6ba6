"""Request waterfall (obs/waterfall.py) with its legs and the compile
listener that files under them (obs/xprof.py), and the HBM residency
ledger (obs/ledger.py).

The tier-1 acceptance story: stamp vectors stay monotone through a real
VerifyService (first-write-wins marks, shared flush clocks), stage
durations tile the e2e wall with unattributed time as a first-class
``other`` stage, the cross-process stash reconstructs one waterfall per
trace id on the client side, the ledger's books match live buffer sizes
through register/donate/delete, and everything is a safe no-op under
``ETH_SPECS_OBS=0``. Legs: the legs of a flush plus ``device.other`` equal
its device stage, a leg outside a flush is a plain span, ledgers are the
dispatch thread's own, and what XLA compiles is filed under the open leg.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from eth_consensus_specs_tpu import obs, serve
from eth_consensus_specs_tpu.obs import ledger, trace, waterfall
from eth_consensus_specs_tpu.obs.registry import Registry
from eth_consensus_specs_tpu.ops import merkle as ops_merkle
from eth_consensus_specs_tpu.serve.config import ServeConfig


@pytest.fixture(autouse=True)
def _fresh_obs_state(monkeypatch):
    """Isolated registry + cleared waterfall stash and ledger books, so
    these tests never pollute the process registry the run-level
    obs_report.json is built from."""
    from eth_consensus_specs_tpu.obs import registry as registry_mod

    waterfall.reset_for_tests()
    ledger.reset_for_tests()
    monkeypatch.setattr(registry_mod, "_REGISTRY", Registry())
    yield
    waterfall.reset_for_tests()
    ledger.reset_for_tests()


@pytest.fixture
def trees():
    rng = np.random.default_rng(7)
    return [rng.integers(0, 256, size=(n, 32)).astype(np.uint8) for n in (1, 5, 17)]


# ------------------------------------------------------------------- marks --


def test_mark_first_write_wins():
    stamps: dict = {}
    waterfall.mark(stamps, "admitted", t=1.0)
    waterfall.mark(stamps, "admitted", t=2.0)  # a hedge can't rewind
    assert stamps["admitted"] == 1.0
    waterfall.mark(None, "admitted")  # None vector is a no-op


def test_mark_all_shares_one_clock_read():
    class R:
        def __init__(self):
            self.stamps = {}

    reqs = [R(), R(), R()]
    waterfall.mark_all(reqs, "device_start")
    ts = {r.stamps["device_start"] for r in reqs}
    assert len(ts) == 1  # one boundary, one clock read


def test_stage_durations_tile_total():
    t0 = 100.0
    stamps = {}
    t = t0
    for name in waterfall.MARKS:
        t += 0.010
        stamps[name] = t
    d = waterfall.stage_durations_ms(t0, stamps)
    named = sum(d[s] for s in waterfall.STAGE_NAMES)
    assert d["total"] == pytest.approx((t - t0) * 1e3)
    assert named + d["other"] == pytest.approx(d["total"])
    assert all(v >= 0 for v in d.values())


def test_stage_durations_missing_marks_land_in_other():
    # error path: resolved without ever dispatching — device stages
    # absent, their time attributed to "other", never silently dropped
    t0 = 10.0
    stamps = {"admitted": 10.001, "queued": 10.002, "resolved": 10.050}
    d = waterfall.stage_durations_ms(t0, stamps)
    assert "device" not in d and "dispatch_wait" not in d
    assert d["other"] == pytest.approx(d["total"] - d["admit"])


def test_stage_durations_empty_until_resolved():
    assert waterfall.stage_durations_ms(0.0, {}) == {}
    assert waterfall.stage_durations_ms(0.0, {"admitted": 0.1}) == {}
    assert waterfall.stage_durations_ms(0.0, None) == {}


# ----------------------------------------------------------- real service --


def test_service_stamps_monotone_and_histograms_populated(trees, monkeypatch):
    """Every request through a real VerifyService produces an ordered
    stamp vector (each mark >= its predecessor, all >= t_submit) and
    stage histograms whose named sums tile the measured e2e wall."""
    captured = []
    real = waterfall.stage_durations_ms

    def spy(t0, stamps):
        if stamps and "resolved" in stamps:
            captured.append((t0, dict(stamps)))
        return real(t0, stamps)

    monkeypatch.setattr(waterfall, "stage_durations_ms", spy)
    from eth_consensus_specs_tpu.serve import buckets

    direct = [
        ops_merkle.merkleize_subtree_device(t, buckets.subtree_depth(t.shape[0]))
        for t in trees
    ]
    with serve.VerifyService(ServeConfig.from_env(max_batch=4, max_wait_ms=5)) as svc:
        futs = [svc.submit_hash_tree_root(t) for t in trees]
        got = [f.result(timeout=60) for f in futs]
    assert got == direct

    assert len(captured) == len(trees)
    for t0, stamps in captured:
        seq = [t0] + [stamps[m] for m in waterfall.MARKS if m in stamps]
        assert stamps.keys() >= set(waterfall.MARKS)  # full pipeline
        assert seq == sorted(seq), f"stamps out of order: {stamps}"

    snap = obs.snapshot()
    rep = waterfall.report(snap)
    for stage in waterfall.STAGE_NAMES + ("other", "total"):
        assert rep["stages"][stage]["count"] == len(trees)
    assert rep["coverage"] is not None and rep["coverage"] >= 0.95
    assert snap["histograms"]["serve.stage_ms.total"]["count"] == len(trees)


def test_cross_process_merge_via_trace_ids(trees):
    """The replica seam: a request submitted under an active trace
    context stashes its durations by trace id; the RPC layer pops them
    (one waterfall, reconstructed client-side) and the front door's
    residual wire stage is client e2e minus the shipped total."""
    import time as _time

    ctx = trace.new_trace()
    with trace.activate(ctx):
        t_client = _time.monotonic()
        with serve.VerifyService(
            ServeConfig.from_env(max_batch=4, max_wait_ms=5)
        ) as svc:
            svc.submit_hash_tree_root(trees[0]).result(timeout=60)
        client_e2e_ms = (_time.monotonic() - t_client) * 1e3
    stages = waterfall.pop(ctx.trace_id)
    assert stages is not None and stages["total"] > 0
    assert set(waterfall.STAGE_NAMES) <= set(stages)
    # the pop CLAIMED it — a second pop (a retry's reply) finds nothing
    assert waterfall.pop(ctx.trace_id) is None
    # the wire residual the front door records is non-negative: the
    # client wall contains the replica's total
    assert client_e2e_ms - stages["total"] >= 0


def test_stash_is_bounded():
    for i in range(waterfall._STASH_CAP + 16):
        waterfall.stash(f"t{i}", {"total": 1.0})
    assert waterfall.stash_size() == waterfall._STASH_CAP
    # oldest evicted, newest retained
    assert waterfall.pop("t0") is None
    assert waterfall.pop(f"t{waterfall._STASH_CAP + 15}") is not None
    assert waterfall.stash(None, {"total": 1.0}) is None  # no-op
    assert waterfall.pop(None) is None


# ------------------------------------------------------------------ ledger --


def test_ledger_accounting_matches_live_buffers():
    a = jnp.zeros((64, 32), jnp.uint8)
    b = jnp.zeros((16, 8), jnp.uint64)
    ledger.register("resident_state", "a", int(a.nbytes))
    ledger.register("merkle_forest", "b", int(b.nbytes))
    assert ledger.resident_bytes("resident_state") == a.nbytes
    assert ledger.resident_bytes() == a.nbytes + b.nbytes
    # replacement is an update, not a leak
    ledger.register("resident_state", "a", int(a.nbytes))
    assert ledger.resident_bytes("resident_state") == a.nbytes
    # donation closes the books and returns the freed bytes
    assert ledger.donate("merkle_forest", "b") == b.nbytes
    assert ledger.resident_bytes("merkle_forest") == 0
    # deletion likewise; unknown entries free nothing
    assert ledger.delete("resident_state", "a") == a.nbytes
    assert ledger.delete("resident_state", "a") == 0
    assert ledger.resident_bytes() == 0
    # the high-water mark survives the deletions
    assert ledger.high_water_bytes() == a.nbytes + b.nbytes
    sec = ledger.postmortem_section()
    assert sec["resident_total_bytes"] == 0
    assert sec["high_water_bytes"] == a.nbytes + b.nbytes


def test_ledger_gauges_and_postmortem_section():
    ledger.register("trusted_setup", "twiddles", 4096)
    ledger.register("trusted_setup", "roots", 1024)
    ledger.register("jit_cache", "state_root", 512)
    gauges = obs.snapshot()["gauges"]
    assert gauges["hbm.resident_bytes.trusted_setup"]["last"] == 5120
    assert gauges["hbm.resident_bytes_total"]["last"] == 5632
    sec = ledger.postmortem_section(top=2)
    assert sec["owners"] == {"trusted_setup": 5120, "jit_cache": 512}
    assert [e["name"] for e in sec["top_entries"]] == ["twiddles", "roots"]
    # pure numeric accounting: nothing env- or argv-shaped in the block
    assert set(sec) == {
        "resident_total_bytes", "high_water_bytes", "owners", "top_entries",
    }


def test_ledger_rides_postmortem_bundle(tmp_path):
    ledger.register("resident_state", "columns", 2048)
    path = obs.flight.dump("waterfall-test", out_dir=str(tmp_path))
    assert path is not None
    import json

    bundle = json.load(open(path))
    assert bundle["hbm"]["resident_total_bytes"] == 2048
    assert bundle["hbm"]["owners"] == {"resident_state": 2048}


def test_ledger_noop_when_obs_disabled(monkeypatch):
    from eth_consensus_specs_tpu.obs import registry as registry_mod

    monkeypatch.setenv("ETH_SPECS_OBS", "0")
    assert registry_mod.refresh_enabled() is False
    try:
        reg = registry_mod.get_registry()
        # the ledger's internal books stay live (tests rely on exact
        # bytes) but publish no gauges
        ledger.register("resident_state", "x", 128)
        assert ledger.resident_bytes() == 128
        assert reg.gauges == {} and reg.counters == {}
    finally:
        monkeypatch.setenv("ETH_SPECS_OBS", "1")
        assert registry_mod.refresh_enabled() is True


# ------------------------------------------------- the device stage's clock --


def _htr_flush(svc):
    rng = np.random.default_rng(11)
    tree = rng.integers(0, 256, size=(5, 32)).astype(np.uint8)
    return [svc.submit_hash_tree_root(tree)], ()


def _bls_flush(svc):
    from eth_consensus_specs_tpu.utils import bls

    msg = b"\x21" * 32
    pks = [bls.SkToPk(s) for s in (1, 2)]
    sig = bls.Aggregate([bls.Sign(s, msg) for s in (1, 2)])
    return [svc.submit_bls_aggregate(pks, msg, sig)], ("bls.keys", "bls.h2c", "bls.pairing")


@pytest.mark.parametrize("flush", [_htr_flush, _bls_flush], ids=["htr", "bls"])
def test_a_served_flush_is_clocked_by_the_device_stage_alone(flush):
    """One clock round the synced dispatch, `serve.stage_ms.device`, split
    by the legs where the kind has them; no histogram under a device's
    name (`device.*`) beside it."""
    with serve.VerifyService(ServeConfig.from_env(max_batch=4, max_wait_ms=5)) as svc:
        futs, legs = flush(svc)
        for f in futs:
            f.result(timeout=120)
    hists = obs.snapshot()["histograms"]
    assert hists["serve.stage_ms.device"]["count"] == len(futs)
    assert hists["serve.stage_ms.device.other"]["count"] == len(futs)
    for leg in legs:
        assert hists[f"serve.stage_ms.device.{leg}"]["count"] == len(futs)
    assert not [name for name in hists if name.startswith("device.")]


# -------------------------------------------------------------------- legs --


class _StubbedService(serve.VerifyService):
    """A service whose `_execute` is the test's: legs round sleeps, no
    kernel and so no compile. Everything round it (batch thread, dispatch
    loop, ledger, resolve) is the program's own."""

    def __init__(self, body, name="serve"):
        self._body = body
        super().__init__(ServeConfig.from_env(max_batch=4, max_wait_ms=5), name=name)

    def _execute(self, reqs, device):
        self._body(self, device)
        return {id(r): b"\x00" * 32 for r in reqs}


def _submit_traced(svc, chunks):
    """One request under a trace context of its own: (result, the stage
    durations `_resolve` stashed for it)."""
    ctx = trace.new_trace()
    with trace.activate(ctx):
        fut = svc.submit_hash_tree_root(chunks)
    fut.result(timeout=30)
    return waterfall.pop(ctx.trace_id)


def _sleep_ms(ms):
    import time

    time.sleep(ms / 1e3)


def test_legs_and_device_other_tile_the_device_stage(trees):
    def body(svc, device):
        with waterfall.leg("t.first"):
            _sleep_ms(20)
        for _ in range(3):  # a leg entered again adds up over the flush
            with waterfall.leg("t.again"):
                _sleep_ms(5)
        _sleep_ms(10)  # under no leg: device.other

    with _StubbedService(body) as svc:
        stages = _submit_traced(svc, trees[0])
    assert stages["device.t.first"] >= 20 and stages["device.t.again"] >= 15
    assert stages["device.other"] >= 10
    legs = [v for k, v in stages.items() if k.startswith("device.")]
    assert sum(legs) == pytest.approx(stages["device"])
    hists = obs.snapshot()["histograms"]
    for name in ("device.t.first", "device.t.again", "device.other"):
        assert hists[f"serve.stage_ms.{name}"]["count"] == 1
        assert hists[f"serve.stage_ms.{name}"]["sum"] == pytest.approx(stages[name])
    spans = obs.snapshot()["spans"]
    assert spans["t.again"]["count"] == 3 and spans["t.again"]["parent"] == "serve.dispatch"
    # the batch thread's two spans, one a flush each (and the wait that
    # close ends)
    assert spans["serve.prep"]["count"] == 1 and spans["serve.batch_wait"]["count"] >= 1


def test_a_flush_without_legs_is_all_device_other(trees):
    with _StubbedService(lambda svc, device: _sleep_ms(5)) as svc:
        stages = _submit_traced(svc, trees[0])
    assert [k for k in stages if k.startswith("device.")] == ["device.other"]
    assert stages["device.other"] == pytest.approx(stages["device"])


def test_leg_outside_a_flush_is_a_plain_span():
    with waterfall.leg("t.solo", items=3) as sp:
        assert waterfall.current_leg() == "t.solo"
        sp.result = None
    assert waterfall.current_leg() is None
    snap = obs.snapshot()
    assert snap["spans"]["t.solo"]["count"] == 1
    assert not [h for h in snap["histograms"] if h.startswith("serve.stage_ms.device")]


def test_a_leg_inside_a_leg_is_a_plain_span():
    ledger = waterfall.open_flush()
    try:
        with waterfall.leg("t.outer"):
            with waterfall.leg("t.inner"):
                assert waterfall.current_leg() == "t.inner"
                _sleep_ms(2)
    finally:
        waterfall.close_flush()
    assert list(ledger) == ["t.outer"] and ledger["t.outer"] >= 2
    assert obs.snapshot()["spans"]["t.inner"]["parent"] == "t.outer"


def test_leg_noop_when_obs_disabled(monkeypatch):
    from eth_consensus_specs_tpu.obs import registry as registry_mod

    monkeypatch.setenv("ETH_SPECS_OBS", "0")
    assert registry_mod.refresh_enabled() is False
    ledger = waterfall.open_flush()
    try:
        with waterfall.leg("t.off") as sp:
            sp.result = 1
            assert waterfall.current_leg() is None
        assert isinstance(sp, registry_mod._NullSpan) and ledger == {}
        reg = registry_mod.get_registry()
        assert reg.spans == {} and reg.histograms == {} and reg.events == []
    finally:
        waterfall.close_flush()
        monkeypatch.setenv("ETH_SPECS_OBS", "1")
        assert registry_mod.refresh_enabled() is True


def test_two_services_keep_separate_ledgers(trees):
    """Both dispatch threads are inside a leg at the same moment (the
    barrier), and each request's stages hold its own service's leg alone."""
    import threading

    both_inside = threading.Barrier(2, timeout=20)

    def body(svc, device):
        with waterfall.leg(f"t.{svc.name}"):
            both_inside.wait()
            _sleep_ms(5)

    with _StubbedService(body, name="one") as one, _StubbedService(body, name="two") as two:
        got: dict = {}
        threads = [
            threading.Thread(target=lambda s=s: got.__setitem__(s.name, _submit_traced(s, trees[0])))
            for s in (one, two)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    for mine, other in (("one", "two"), ("two", "one")):
        assert got[mine][f"device.t.{mine}"] >= 5
        assert f"device.t.{other}" not in got[mine]
        assert got[mine][f"device.t.{mine}"] + got[mine]["device.other"] == pytest.approx(
            got[mine]["device"])


def test_a_degraded_flush_adds_every_attempt(trees):
    """`fault.degrade` runs the device path twice (one retry) and then the
    host path: one ledger, the three attempts added."""
    calls = []

    def body(svc, device):
        calls.append(device)
        with waterfall.leg("t.attempt"):
            _sleep_ms(10)
            if device:
                raise MemoryError("the device gave out")

    with _StubbedService(body) as svc:
        stages = _submit_traced(svc, trees[0])
    assert calls == [True, True, False]
    assert stages["device.t.attempt"] >= 30
    assert stages["device.t.attempt"] + stages["device.other"] == pytest.approx(stages["device"])
    # a leg whose body raised is in the ledger (the time was spent) though
    # not among the span aggregates, as for any span
    assert obs.snapshot()["spans"]["t.attempt"]["count"] == 1


def test_compile_listener_files_a_compile_under_the_open_leg():
    import jax

    from eth_consensus_specs_tpu.obs import xprof

    xprof.install_compile_listener()
    xprof.install_compile_listener()  # once a process: no second listener
    x = np.arange(7.0)  # a host array: making it compiles nothing

    def fresh_under_leg(v):
        return v * 3.0 + 0.125

    def fresh_outside(v):
        return v * 5.0 - 0.375

    with waterfall.leg("t.compiling"):
        jax.jit(fresh_under_leg)(x).block_until_ready()
    jax.jit(fresh_outside)(x).block_until_ready()
    hists = obs.snapshot()["histograms"]
    assert hists["xla.compile_ms.t.compiling"]["count"] == 1
    assert hists["xla.compile_ms.none"]["count"] == 1
    events = [e for e in obs.get_registry().events if e["kind"] == "xla.compile"]
    assert [(e["leg"], "fresh_under_leg" in e["fun_name"], "fresh_outside" in e["fun_name"])
            for e in events] == [("t.compiling", True, False), ("none", False, True)]
    assert all(e["ms"] > 0 and e["cache_hit"] is False for e in events)
