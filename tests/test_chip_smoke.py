"""chip_smoke.py rehearsed on the CPU: the phases after the device check at
a tiny mainnet-SHAPED size (64 validators / 32 slots / 2 committees = 1
member, a sync committee of the whole registry, full-size blobs), and the
checks that make the smoke worth running: it refuses a CPU backend, it
fails when anything was answered from a fallback, a compile refusal reaches
the caller, and the compile cache goes where the environment says."""

from __future__ import annotations

import contextlib
import io
import json

import numpy as np
import pytest

import chip_smoke
from eth_consensus_specs_tpu import fault, obs
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.serve.config import ServeConfig
from eth_consensus_specs_tpu.serve.service import VerifyService
from eth_consensus_specs_tpu.utils import cache

TINY = chip_smoke.Sizes(
    validators=64, committees=2, committee_size=1, sync_size=64, blobs=2,
    htr_trees=2, htr_depth=4,
)


@pytest.fixture(scope="module")
def rehearsal():
    """One run of every phase after ``device`` (which is what a CPU run is
    expected to fail); the printed phase lines, parsed."""
    buckets.reset_for_tests()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        chip_smoke.run_phases(TINY, seed=0)
    return [json.loads(line) for line in out.getvalue().splitlines()]


def test_rehearsal_runs_every_phase_in_order(rehearsal):
    assert [r["phase"] for r in rehearsal] == ["boot", "stateless", "slots", "no_fallback"]
    assert all(r["seconds"] >= 0 for r in rehearsal)


def test_rehearsal_world_is_the_mainnet_preset(rehearsal):
    boot = rehearsal[0]
    assert (boot["fork"], boot["preset"]) == ("altair", "mainnet")
    assert boot["validators"] == TINY.validators and boot["resident_bytes"] > 0
    # the full recompute through submit_state_root, the forest root of the
    # boot and the host oracle are one root
    assert rehearsal[1]["state_root"] == boot["root"]


def test_rehearsal_slots_refuse_exactly_the_invalid_items(rehearsal):
    slots = rehearsal[2]
    assert slots["slots"] == chip_smoke.SLOTS and slots["epoch"] == 1
    assert slots["refused"] == {"attestations": 1, "blobs": 1}
    assert slots["root"] != rehearsal[0]["root"]


def test_rehearsal_compiles_each_family_once(rehearsal):
    """Three slots, an invalid item in two of them, a boundary: every flush
    lands in its family's one bucket (the bisection pads into the flush's
    own, the aggregation keys on the request, prewarm warms the bucket of
    a full slot)."""
    assert rehearsal[3]["families"] == sorted(
        ["fr_fft", "g2_agg", "kzg", "merkle_many", "resident", "resident_root",
         "slot_apply", "state_root"]
    )
    assert rehearsal[3]["serve_compiles"] >= 8


@pytest.mark.parametrize(
    "counter",
    ["fault.degraded", "fault.degraded.slot.reroot", "serve.degraded_items",
     "slot.forest_rebuilds"],
)
def test_no_fallback_fails_when_a_fallback_answered(monkeypatch, counter):
    monkeypatch.setattr(obs, "snapshot", lambda: {"counters": {counter: 1, "slot.slots": 3}})
    with pytest.raises(chip_smoke.SmokeFailure, match=counter):
        chip_smoke.phase_no_fallback(None, TINY, {})


def test_no_fallback_reports_a_family_that_compiled_twice(monkeypatch, rehearsal):
    class Svc:
        config = ServeConfig()

        def slot_world(self):
            class World:
                def resident_arrays(self):
                    return []

            return World()

    want = chip_smoke.expected_compile_keys(TINY, Svc())
    monkeypatch.setattr(buckets, "seen_shapes", lambda: sorted(want | {("kzg", 4)}))
    monkeypatch.setattr(obs, "snapshot", lambda: {"counters": {}})
    with pytest.raises(chip_smoke.SmokeFailure, match=r"compiled twice \['kzg'\]"):
        chip_smoke.phase_no_fallback(Svc(), TINY, {})


def test_device_phase_refuses_a_cpu_backend(capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="no accelerator"):
        chip_smoke.phase_device(1)
    with pytest.raises(chip_smoke.SmokeFailure, match="no accelerator"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_phases_rehearsed_on_virtual_devices(capsys):
    """``--chips 4`` on four of the suite's virtual CPU devices at a small
    registry: the served flush shards (and the one-chip service's does
    not), sharded == unsharded == host, every sharded array on 4 devices."""
    chip_smoke.phase_mesh(seed=0, chips=4, validators=256, step_depth=10)
    serve, step = (json.loads(line) for line in capsys.readouterr().out.splitlines())
    assert serve["phase"] == "mesh_serve" and serve["sharded_dispatches"] >= 1
    assert ["merkle_many", chip_smoke.MESH_TREES, chip_smoke.MESH_TREE_DEPTH,
            "cpu2x2"] in serve["keys"]
    assert step["phase"] == "mesh_step"
    assert set(step["shard_devices"].values()) == {4}


# ------------------------------------------------------- compile cache --


@pytest.fixture
def fake_accelerator(monkeypatch):
    """enable_persistent_cache as an accelerator process would run it, with
    the config writes recorded instead of applied."""
    import jax

    writes = {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update", lambda k, v: writes.__setitem__(k, v))
    monkeypatch.setattr(cache, "_enabled", False)
    return writes


def test_cache_dir_from_the_environment_is_used_and_none_set_in_code(
    monkeypatch, tmp_path, fake_accelerator
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert cache.enable_persistent_cache() == str(tmp_path / "cc")
    assert (tmp_path / "cc").is_dir()
    assert "jax_compilation_cache_dir" not in fake_accelerator


def test_cache_dir_defaults_to_the_fixed_checkout_path(monkeypatch, fake_accelerator):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = cache.default_cache_dir()
    assert want.endswith("/.jax_cache") and cache.cache_dir_path() == want
    assert cache.enable_persistent_cache() == want
    assert fake_accelerator["jax_compilation_cache_dir"] == want
    # fixed: the same path from every call and every process
    assert cache.default_cache_dir() == want


def test_cache_stays_off_on_the_cpu_backend_and_a_dead_backend_raises(monkeypatch):
    import jax

    assert cache.enable_persistent_cache() is None  # XLA:CPU entries are not portable

    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", dead)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        cache.enable_persistent_cache()


# ------------------------------------- no fallback that hides the device --


class XlaRuntimeError(RuntimeError):
    """Stand-in with the runtime's type name (classification reads it)."""


def _tree(seed: int = 3):
    return np.random.default_rng(seed).integers(0, 256, (16, 32), dtype=np.uint8)


def test_compile_refusal_at_first_dispatch_reaches_the_caller(monkeypatch):
    """The compiler refusing a served kernel is an error at the future, not
    a correct answer from the host with only a counter to show for it."""
    from eth_consensus_specs_tpu.ops import merkle

    def refuse(*a, **k):
        raise XlaRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: bad layout")

    monkeypatch.setattr(merkle, "merkleize_many_device", refuse)
    before = obs.snapshot()["counters"].get("serve.degraded_items", 0)
    svc = VerifyService(ServeConfig(max_wait_ms=1.0, mesh_chips=1))
    try:
        with pytest.raises(XlaRuntimeError, match="failed to compile"):
            svc.submit_hash_tree_root(_tree()).result(timeout=60)
    finally:
        svc.close()
    assert obs.snapshot()["counters"].get("serve.degraded_items", 0) == before


def test_injected_device_fault_still_degrades_to_the_host_oracle():
    from eth_consensus_specs_tpu.obs.watchdog import host_tree_root_words
    from eth_consensus_specs_tpu.ops.merkle import _chunks_to_words

    tree = _tree(4)
    before = obs.snapshot()["counters"].get("serve.degraded_items", 0)
    svc = VerifyService(ServeConfig(max_wait_ms=1.0, mesh_chips=1))
    try:
        with fault.injected("serve.dispatch:raise:times=inf"):
            got = svc.submit_hash_tree_root(tree).result(timeout=60)
    finally:
        svc.close()
    assert got == host_tree_root_words(_chunks_to_words(tree, 16))
    assert obs.snapshot()["counters"]["serve.degraded_items"] - before == 1
