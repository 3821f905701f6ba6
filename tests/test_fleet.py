"""Two-tier fleet: heterogeneous replicas × mesh in one front door.

Contract under test (serve/frontdoor.py + router.py + buckets.py +
prejax.py): a 1-chip and a mesh-sliced replica coexist in one fleet,
each spawned with its OWN forced device count; the router keys on
(compile-shape, mesh-signature) — big requests land on the wide tier,
toy requests on the narrow one, and a replica that would cold-compile a
shape is never picked while a warm sibling is routable; a SIGKILLed
replica's respawned replacement replays ONLY its own mesh's warmup
keys; and the SLO evaluator's second actuator demonstrably grows and
retires replicas.

The spawn-heavy tests share ONE module-scoped heterogeneous fleet (the
SIGKILL test runs last in the module and leaves the fleet healed).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import threading
import time

import numpy as np
import pytest

from eth_consensus_specs_tpu import obs, prejax
from eth_consensus_specs_tpu.ops import merkle as ops_merkle
from eth_consensus_specs_tpu.parallel import mesh_ops
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.serve.config import FrontDoorConfig, ServeConfig
from eth_consensus_specs_tpu.serve.frontdoor import FrontDoor
from eth_consensus_specs_tpu.serve.router import Router

TOY_DEPTH = 5
WIDE_DEPTH = 9  # 512 chunks x max_batch 4 = 2048 clears MESH_SUBTREE_THRESHOLD
WIDE_CHIPS = 2
WIDE_SIG = "cpu1x2"  # make_mesh(2) lays (dp, sp) = (1, 2)


def _counter(name: str) -> float:
    return obs.snapshot()["counters"].get(name, 0)


def _serve_cfg(**kw) -> ServeConfig:
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 5)
    kw.setdefault("buckets", (1, 2, 4))
    return ServeConfig.from_env(**kw)


def _fd_cfg(**kw) -> FrontDoorConfig:
    kw.setdefault("hedge_ms", 0.0)
    kw.setdefault("probe_interval_ms", 100.0)
    kw.setdefault("slo_shedding", False)
    return FrontDoorConfig.from_env(**kw)


def _trees(n: int, depth: int, seed: int = 0) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    cap = 1 << depth
    return [
        rng.integers(0, 256, size=(int(rng.integers(cap // 2 + 1, cap + 1)), 32))
        .astype(np.uint8)
        for _ in range(n)
    ]


def _direct(trees: list, depth: int) -> list:
    return [ops_merkle.merkleize_subtree_device(t, depth) for t in trees]


# ------------------------------------------------------------------ units --


def test_prejax_replica_chips_env_is_authoritative():
    """A spawned replica inherits the parent's XLA_FLAGS; its own chip
    count must REPLACE an inherited device-count flag, not defer."""
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8 --keep"}
    out = prejax.replica_chips_env(2, env)
    assert out == {"XLA_FLAGS": "--keep --xla_force_host_platform_device_count=2"}
    # chips=1 strips the flag entirely (platform default = one device)
    assert prejax.replica_chips_env(1, env) == {"XLA_FLAGS": "--keep"}
    # off-cpu the device count is real hardware: leave it alone
    assert prejax.replica_chips_env(8, {"JAX_PLATFORMS": "tpu"}) == {}


def test_prejax_preparse_chips_replicas_matrix():
    argv = ["x", "--chips", "4", "--replicas=3", "--chips-matrix", "1,8"]
    assert prejax.parse_chips(argv) == 4
    assert prejax.parse_replicas(argv) == 3
    assert prejax.parse_chips_matrix(argv) == (1, 8)
    assert prejax.parse_chips_matrix(["x"]) == ()


def test_profile_key_fns_agree_with_mesh_key_fns():
    """The router predicts sibling compile keys from (shards, sig); the
    profile-form and mesh-form of the LIVE key fns must agree (jaxlint's
    recompile-surface grid runs both — this is the in-tree pin)."""
    cfg = (1, 2, 4, 8)
    for n in (1, 3, 5, 8):
        for depth in (5, 9, 12):
            assert buckets.merkle_many_key(n, depth, cfg, mesh=None) == (
                buckets.merkle_many_key_from_profile(n, depth, cfg, 1, "")
            )
    assert buckets.merkle_many_key_from_profile(3, 9, cfg, 2, WIDE_SIG) == (
        "merkle_many", buckets.mesh_batch_bucket(3, 2, cfg), 9, WIDE_SIG
    )
    for items, lanes in ((1, 3), (5, 8), (9, 64)):
        assert buckets.bls_msm_key(items, lanes, mesh=None) == (
            buckets.bls_msm_key_from_profile(items, lanes, 1, "")
        )


def test_route_wide_policy_matches_mesh_crossover():
    """Big flushes belong on the wide tier exactly when the steady-state
    flush clears the measured mesh crossover; toy flushes never do."""
    assert buckets.route_wide("htr", WIDE_DEPTH, 4)  # 512*4 >= 2048
    assert not buckets.route_wide("htr", TOY_DEPTH, 4)  # 32*4 = 128
    assert not buckets.route_wide("htr", WIDE_DEPTH, 1)  # 512*1 < 2048
    assert buckets.route_wide("bls", 4, 8)  # item-axis sharding: full flush


def test_widen_warm_keys_signs_only_worthwhile_pads():
    cfg = _serve_cfg()
    base = [("merkle_many", b, WIDE_DEPTH) for b in cfg.buckets] + [
        ("merkle_many", b, TOY_DEPTH) for b in cfg.buckets
    ]
    narrow = buckets.widen_warm_keys(base, cfg, 1, "")
    assert narrow == [tuple(k) for k in base]
    wide = buckets.widen_warm_keys(base, cfg, 2, WIDE_SIG)
    signed = [k for k in wide if len(k) == 4]
    assert signed  # the wide depth gets mesh-signed pads...
    assert all(k[3] == WIDE_SIG for k in signed)
    # ...but the toy depth shards never (sub-crossover at every flush)
    assert all(k[2] == WIDE_DEPTH for k in signed if k[0] == "merkle_many")
    assert len(set(wide)) == len(wide)  # deduped


def test_router_tier_warm_and_retire():
    """Pure-router policy: wide requests land on the wide tier, the
    warm-cache map vetoes cold candidates while a warm sibling exists,
    retired slots never route, and with no profiles the original
    affinity walk is unchanged."""
    r = Router(3)
    r.set_profile(0, chips=1, signature="", warm_keys=[("merkle_many", 2, TOY_DEPTH)])
    r.set_profile(1, chips=WIDE_CHIPS, signature=WIDE_SIG,
                  warm_keys=[("merkle_many", 4, WIDE_DEPTH, WIDE_SIG),
                             ("merkle_many", 2, WIDE_DEPTH)])
    r.set_profile(2, chips=1, signature="", warm_keys=[("merkle_many", 2, TOY_DEPTH)])
    for _ in range(8):
        assert r.pick(("merkle_many", WIDE_DEPTH), wide=True) == 1
        assert r.pick(("merkle_many", TOY_DEPTH), wide=False) in (0, 2)
    # warm veto: the wide replica is the ONLY one warm for the wide
    # shape, so even with NO tier preference the cold candidates lose
    assert r.pick(("merkle_many", WIDE_DEPTH), wide=None) == 1
    r.set_retired(1, True)
    assert r.pick(("merkle_many", WIDE_DEPTH), wide=True) != 1
    r.set_retired(1, False)
    assert r.pick(("merkle_many", WIDE_DEPTH), wide=True) == 1
    idx = r.add_replica()
    assert idx == 3 and len(r) == 4
    snap = r.snapshot()
    assert snap[1]["chips"] == WIDE_CHIPS and snap[1]["signature"] == WIDE_SIG
    assert snap[1]["picks"] > 0


def test_frontdoor_config_fleet_knobs(monkeypatch):
    monkeypatch.setenv("ETH_SPECS_SERVE_CHIPS_MATRIX", "1,8")
    monkeypatch.setenv("ETH_SPECS_SERVE_DOWN_COOLDOWN_MS", "250")
    monkeypatch.setenv("ETH_SPECS_SERVE_DRAINING_TTL_S", "2.5")
    monkeypatch.setenv("ETH_SPECS_SERVE_AUTOSCALE", "1")
    monkeypatch.setenv("ETH_SPECS_SERVE_MAX_REPLICAS", "5")
    monkeypatch.setenv("ETH_SPECS_SERVE_GROW_WINDOWS", "2")
    monkeypatch.setenv("ETH_SPECS_SERVE_RETIRE_WINDOWS", "7")
    monkeypatch.setenv("ETH_SPECS_SERVE_SCALE_COOLDOWN_S", "0.5")
    monkeypatch.setenv("ETH_SPECS_SERVE_MIN_REPLICAS", "2")
    cfg = FrontDoorConfig.from_env()
    assert cfg.chips_matrix == (1, 8)
    assert [cfg.chips_for(i) for i in range(4)] == [1, 8, 1, 8]
    assert cfg.down_cooldown_s == 0.25
    assert cfg.draining_ttl_s == 2.5
    assert cfg.autoscale and cfg.max_replicas == 5 and cfg.min_replicas == 2
    assert cfg.grow_windows == 2 and cfg.retire_windows == 7
    assert cfg.scale_cooldown_s == 0.5
    assert FrontDoorConfig().chips_for(3, 4) == 4  # empty matrix: default


# ------------------------------------------------- heterogeneous fleet --


@pytest.fixture(scope="module")
def het_fd(tmp_path_factory):
    """One heterogeneous fleet for the spawn-heavy tests: a 1-chip and a
    2-chip replica, each pre-warmed for both depths under ITS profile."""
    tmp = tmp_path_factory.mktemp("fleet")
    warm = [("merkle_many", b, d) for d in (TOY_DEPTH, WIDE_DEPTH) for b in (1, 2, 4)]
    fd = FrontDoor(
        replicas=2,
        chips=[1, WIDE_CHIPS],
        config=_serve_cfg(),
        fd_config=_fd_cfg(),
        warmup_path=str(tmp / "warmup.jsonl"),
        warm_keys=warm,
        name="fleet-test",
    )
    try:
        yield fd
    finally:
        fd.close()


def _wait_probed(fd, n: int, timeout: float = 20.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sum(1 for s in fd.replica_stats() if s is not None) >= n:
            return
        time.sleep(0.1)
    raise AssertionError("fleet never fully probed")


def test_het_profiles_parity_and_zero_cold_compiles(het_fd):
    """Both tiers report their mesh profile; toy and wide requests are
    bit-identical to direct ops calls; nothing cold-compiles after
    ready on either tier."""
    fd = het_fd
    profiles = fd.replica_profiles()
    assert profiles[0]["signature"] == "" and profiles[0]["chips"] == 1
    assert profiles[1]["signature"] == WIDE_SIG
    assert profiles[1]["shards"] == WIDE_CHIPS
    toy, wide = _trees(6, TOY_DEPTH, 1), _trees(6, WIDE_DEPTH, 2)
    futs = [fd.submit_hash_tree_root(t) for t in toy + wide]
    got = [f.result(timeout=120) for f in futs]
    assert got == _direct(toy, TOY_DEPTH) + _direct(wide, WIDE_DEPTH)
    _wait_probed(fd, 2)
    time.sleep(fd.fdcfg.probe_interval_s * 3)
    for s in fd.replica_stats():
        assert s is not None and s["compiles_after_ready"] == 0


def test_big_requests_land_on_the_wide_replica(het_fd):
    """Signature-aware routing: wide-classified requests go to the mesh
    tier (frontdoor.route.affinity/mesh_affinity assert), toy requests
    to the narrow tier — observable per-replica via router picks."""
    fd = het_fd
    before = {r["signature"]: r["picks"] for r in fd.router.snapshot()}
    mesh_aff0 = _counter("frontdoor.route.mesh_affinity")
    aff0 = _counter("frontdoor.route.affinity")
    wide = _trees(8, WIDE_DEPTH, 3)
    got = [fd.submit_hash_tree_root(t).result(timeout=120) for t in wide]
    assert got == _direct(wide, WIDE_DEPTH)
    after = {r["signature"]: r["picks"] for r in fd.router.snapshot()}
    assert after[WIDE_SIG] - before[WIDE_SIG] >= len(wide)
    assert after[""] == before[""]  # narrow tier saw none of them
    assert _counter("frontdoor.route.mesh_affinity") - mesh_aff0 >= len(wide)
    assert _counter("frontdoor.route.affinity") >= aff0  # monotone sanity
    toy = _trees(4, TOY_DEPTH, 4)
    got = [fd.submit_hash_tree_root(t).result(timeout=120) for t in toy]
    assert got == _direct(toy, TOY_DEPTH)
    final = {r["signature"]: r["picks"] for r in fd.router.snapshot()}
    assert final[""] - after[""] >= len(toy)  # toys stayed narrow


def test_sigkill_respawn_replays_only_its_own_keys(het_fd):
    """SIGKILL the wide replica mid-load: zero requests lost, bit
    parity held, and the respawned replacement replays ONLY its own
    mesh-signed warmup keys (runs last: leaves the fleet healed)."""
    fd = het_fd
    wide = _trees(10, WIDE_DEPTH, 6)
    want = _direct(wide, WIDE_DEPTH)
    victim_pid = fd._procs[1].pid
    results: list = [None] * len(wide)

    def submit_all():
        for i, t in enumerate(wide):
            results[i] = fd.submit_hash_tree_root(t).result(timeout=180)

    th = threading.Thread(target=submit_all, daemon=True)
    th.start()
    time.sleep(0.15)  # let a few land, then kill mid-load
    os.kill(victim_pid, signal.SIGKILL)
    th.join(timeout=240)
    assert not th.is_alive()
    assert results == want  # zero lost, bit-identical through the failover
    # wait for the supervised respawn + its profile reinstall
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        proc = fd._procs[1]
        if proc is not None and proc.is_alive() and proc.pid != victim_pid:
            if (fd.replica_profiles()[1] or {}).get("warm_keys"):
                break
        time.sleep(0.2)
    profile = fd.replica_profiles()[1]
    assert profile and profile["signature"] == WIDE_SIG
    signed = [k for k in profile["warm_keys"] if any(isinstance(d, str) for d in k[1:])]
    assert signed  # it replayed its own mesh-signed keys...
    assert all(WIDE_SIG in k for k in signed)  # ...and ONLY its own
    assert _counter("frontdoor.replicas_replaced") >= 1
    # the replacement is warm: traffic through it pays no cold compile
    time.sleep(fd.fdcfg.probe_interval_s * 3)
    more = _trees(4, WIDE_DEPTH, 8)
    got = [fd.submit_hash_tree_root(t).result(timeout=120) for t in more]
    assert got == _direct(more, WIDE_DEPTH)
    time.sleep(fd.fdcfg.probe_interval_s * 3)
    stats = fd.replica_stats()
    assert stats[1] is not None and stats[1]["compiles_after_ready"] == 0


def test_autoscaler_grows_then_retires(tmp_path, monkeypatch):
    """The SLO evaluator's second actuator end to end: a sustained
    (forced) p99 breach grows a pre-warmed replica; a sustained idle
    window retires it through the zero-shed drain rollover."""
    monkeypatch.setenv("ETH_SPECS_SLO_WAIT_P99_MS", "0.001")
    fd = FrontDoor(
        replicas=1,
        chips=[1],
        config=_serve_cfg(),
        fd_config=_fd_cfg(
            probe_interval_ms=80.0,
            slo_shedding=False,  # isolate the SECOND actuator
            autoscale=True,
            min_replicas=1,
            max_replicas=2,
            grow_windows=1,
            retire_windows=2,
            scale_cooldown_s=0.3,
        ),
        warmup_path=str(tmp_path / "warmup.jsonl"),
        warm_keys=[("merkle_many", b, TOY_DEPTH) for b in (1, 2, 4)],
        name="fleet-scale",
    )
    try:
        toy = _trees(4, TOY_DEPTH, 9)
        want = _direct(toy, TOY_DEPTH)
        grown0 = _counter("frontdoor.replicas_grown")
        retired0 = _counter("frontdoor.replicas_retired")
        deadline = time.monotonic() + 60
        while _counter("frontdoor.replicas_grown") == grown0:
            assert time.monotonic() < deadline, "autoscaler never grew"
            # every window carries waits, every wait breaches 0.001ms
            assert [fd.submit_hash_tree_root(t).result(timeout=60) for t in toy] == want
            time.sleep(fd.fdcfg.probe_interval_s)
        assert len(fd.live_replicas()) == 2
        monkeypatch.setenv("ETH_SPECS_SLO_WAIT_P99_MS", "250")
        deadline = time.monotonic() + 60
        while _counter("frontdoor.replicas_retired") == retired0:
            assert time.monotonic() < deadline, "autoscaler never retired"
            time.sleep(fd.fdcfg.probe_interval_s)  # idle: no traffic
        assert len(fd.live_replicas()) == 1
        # the survivor still serves, bit-identically
        assert [fd.submit_hash_tree_root(t).result(timeout=60) for t in toy] == want
    finally:
        fd.close()