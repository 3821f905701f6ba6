"""Whole-slot pipeline (ops/slot_pipeline + serve/slot): submit_slot
bit-parity against the sequential host fold, degrade-ladder atomicity at
the slot.verify / slot.reroot fault sites, durable commit + restore with
idempotent replay, the serve-tier threading (phases in the waterfall,
typed Overloaded), and the compile-key discipline (request-derived
capacities, zero cold compiles on a warm shape).

Fast lane: pure host logic — capacities, scatter planning, compile-key
injectivity, result wire codec, site registration. Slow lane (nightly,
like the rest of the device-crypto suite): everything that boots a
world (run_epochs + slot_apply compiles are minutes-scale on CPU)."""

from __future__ import annotations

import shutil
import tempfile
from dataclasses import replace

import numpy as np
import pytest

import __graft_entry__ as graft
import jax
from eth_consensus_specs_tpu import fault
from eth_consensus_specs_tpu.ops import slot_pipeline as sp
from eth_consensus_specs_tpu.ops.state_root import synthetic_static
from eth_consensus_specs_tpu.serve import buckets
from eth_consensus_specs_tpu.utils import bls

N = 64


# ------------------------------------------------------------ test data --


def make_att(subnet, committee, bits, root, bad=False):
    signers = [vi for vi, b in zip(committee, bits) if b]
    sks = [1000 + vi for vi in signers]
    pubkeys = tuple(bytes(bls.SkToPk(sk)) for sk in sks)
    sig = bytes(bls.Aggregate([bls.Sign(sk, root) for sk in sks]))
    if bad:
        sig = bytes(bls.Sign(9999, root))
    return sp.SlotAttestation(
        subnet=subnet, root=root, committee=tuple(committee), bits=tuple(bits),
        pubkeys=pubkeys, sig=sig,
    )


def make_req(slot, boundary=False, bad_att=False, blobs=0, bad_blob=False):
    r1 = b"\x11" * 32
    atts = (
        make_att(3, [1, 2, 3, 4], [1, 1, 0, 1], r1),
        make_att(3, [5, 6], [1, 1], r1),
        make_att(7, [8, 9, 10], [1, 0, 1], b"\x22" * 32, bad=bad_att),
    )
    sync_sks = [2000 + i for i in range(4)]
    sync_msg = b"\x33" * 32
    sync_pk = tuple(bytes(bls.SkToPk(sk)) for sk in sync_sks)
    sync_sig = bytes(bls.Aggregate([bls.Sign(sk, sync_msg) for sk in sync_sks]))
    blob_items = []
    if blobs:
        import hashlib

        from eth_consensus_specs_tpu.crypto import kzg

        for i in range(blobs):
            out = []
            for j in range(kzg.FIELD_ELEMENTS_PER_BLOB):
                h = hashlib.sha256(bytes([i]) + j.to_bytes(4, "big")).digest()
                out.append((int.from_bytes(h, "big") % kzg.BLS_MODULUS).to_bytes(32, "big"))
            blob = b"".join(out)
            c = kzg.blob_to_kzg_commitment(blob)
            p = kzg.compute_blob_kzg_proof(blob, c)
            if bad_blob and i == 0:
                blob = blob[:-1] + bytes([blob[-1] ^ 1])
            blob_items.append((blob, bytes(c), bytes(p)))
    return sp.SlotRequest(
        slot=slot, attestations=atts, sync_pubkeys=sync_pk, sync_message=sync_msg,
        sync_sig=sync_sig, sync_indices=(11, 12, 13, 14), blobs=tuple(blob_items),
        epoch_boundary=boundary,
    )


def dummy_req(slot=0, bits=((1, 1, 0, 1),), sync=4):
    """A shape-only request (garbage signatures): enough for capacity /
    key / planning tests that never verify anything."""
    atts = tuple(
        sp.SlotAttestation(
            subnet=i, root=b"\x00" * 32,
            committee=tuple(range(len(b))), bits=tuple(b),
            pubkeys=tuple(b"\x00" * 48 for bit in b if bit), sig=b"\x00" * 96,
        )
        for i, b in enumerate(bits)
    )
    return sp.SlotRequest(
        slot=slot, attestations=atts, sync_pubkeys=(), sync_message=b"\x00" * 32,
        sync_sig=b"\x00" * 96, sync_indices=tuple(range(sync)), blobs=(),
        epoch_boundary=False,
    )


def host_oracle(reqs, n=N):
    spec = sp.slot_spec()
    cols, just = graft._example_altair_inputs(n)
    static = synthetic_static(spec, n)
    cols, just = jax.device_put(cols), jax.device_put(just)
    epoch, results = 0, []
    for req in reqs:
        res, cols, just = sp.host_slot_fold(spec, static, cols, just, req, epoch)
        epoch = res.epoch
        results.append(res)
    return results


# ------------------------------------------------------------ fast lane --


def test_request_capacity_is_pre_verdict_shape_only():
    """Capacity counts every SET committee bit and every sync index —
    before any verdict exists — so the front door's routing key and the
    dispatch's compile key derive from the request alone."""
    req = dummy_req(bits=((1, 1, 0, 1), (1, 0)), sync=4)
    assert sp.request_capacity(req) == (4, 4)
    assert sp.request_capacity(dummy_req(bits=(), sync=0)) == (0, 0)


def test_slot_key_buckets_capacities_pow2():
    from eth_consensus_specs_tpu.ops.state_root import forest_plan

    _, meta = synthetic_static(sp.slot_spec(), N)
    plan = forest_plan(meta)
    k5 = buckets.slot_key(N, 5, 3, plan)
    k8 = buckets.slot_key(N, 8, 4, plan)
    assert k5 == k8  # both capacities bucket up to the same pow2 lanes
    assert k5[0] == "slot_apply" and k5[1] == N
    assert buckets.slot_key(N, 9, 4, plan) != k8  # 9 escapes the 8-bucket
    assert buckets.slot_key(N, 0, 0, plan)[2:4] == (1, 1)  # empty never 0-lane


def test_plan_updates_uses_valid_items_only():
    req = dummy_req(bits=((1, 1, 0, 1), (1, 0)), sync=3)
    flag_idx, reward_idx, reward_amt = sp.plan_updates(req, [True, False], True, N)
    assert sorted(flag_idx.tolist()) == [0, 1, 3]  # second att rejected
    assert reward_idx.tolist() == [0, 1, 2]
    assert np.all(reward_amt == sp.sync_reward_gwei())
    # rejected sync verdict: no rewards at all
    _, r_idx, r_amt = sp.plan_updates(req, [True, True], False, N)
    assert len(r_idx) == 0 and len(r_amt) == 0
    # out-of-registry indices are dropped, never scattered; duplicates
    # survive (the kernel's scatter-ADD hit count is duplicate-safe)
    f2, _, _ = sp.plan_updates(req, [True, True], True, 2)
    assert sorted(f2.tolist()) == [0, 0, 1]


def test_slot_result_wire_codec_roundtrip():
    from eth_consensus_specs_tpu.serve.slot import _result_from_json, _result_json

    res = sp.SlotResult(
        slot=7, att_verdicts=(True, False), sync_verdict=True,
        blob_verdicts=(True,), subnet_aggregates=((3, b"\xaa" * 96),),
        state_root=b"\x42" * 32, epoch=2, replayed=False,
    )
    back = _result_from_json(_result_json(res))
    assert back == res
    # `replayed` is NOT wire state: the dedup window stores the original
    # commit and the flag is stamped at replay time, never persisted
    assert not _result_from_json(_result_json(replace(res, replayed=True))).replayed


def test_slot_world_booting_busy_is_honest(tmp_path):
    """An eager boot in flight answers busy with the measured previous
    boot wall (the ResidentOwner restore-ETA convention) — mid-boot
    submits must never park in the listener backlog. The lazy path
    (no mark_booting) never reports busy."""
    from eth_consensus_specs_tpu.serve.slot import SlotWorld

    w = SlotWorld(n_validators=8, ckpt_dir=str(tmp_path))
    assert not w.busy  # lazy path: nothing eager in flight
    w.mark_booting()
    assert w.busy
    # no measured boot yet: the fallback ETA floors the hint
    assert w.retry_after_s() > 0
    st = w.status()
    assert st["booting"] and st["retry_after_s"] > 0
    # a completed boot persists its wall; the NEXT world's hint is the
    # measured number, not the fallback
    w._persist_eta(7.5)
    w2 = SlotWorld(n_validators=8, ckpt_dir=str(tmp_path))
    assert w2._eta_s == 7.5
    w2.mark_booting()
    assert 0 < w2.retry_after_s() <= 7.5
    # boot completion clears busy (simulated: the flag pair, not a real
    # boot — the slow lane covers the full restore path)
    w2._booted = True
    assert not w2.busy and not w2.status()["booting"]


def test_slot_fault_sites_are_registered():
    from eth_consensus_specs_tpu.fault import sites

    for name in ("slot.verify", "slot.reroot"):
        assert sites.declared(name), name
        assert "raise" in sites.SITES[name].modes


# ------------------------------------------------------------ slow lane --


@pytest.fixture(scope="module")
def slot_reqs():
    return [
        make_req(0, blobs=1),
        make_req(1, bad_att=True),
        make_req(2, blobs=1, bad_blob=True),
        make_req(3, boundary=True),
    ]


@pytest.fixture(scope="module")
def oracle(slot_reqs):
    return host_oracle(slot_reqs)


def _assert_result_parity(d, w):
    assert d.att_verdicts == w.att_verdicts
    assert d.sync_verdict == w.sync_verdict
    assert d.blob_verdicts == w.blob_verdicts
    assert d.subnet_aggregates == w.subnet_aggregates
    assert d.state_root == w.state_root, (d.slot, d.state_root.hex(), w.state_root.hex())
    assert d.epoch == w.epoch


@pytest.mark.slow
def test_submit_slot_bit_parity_vs_sequential_host_fold(slot_reqs, oracle):
    """Valid, invalid-attestation, invalid-blob and epoch-boundary slots
    through the device pipeline — every verdict, aggregate, and post-slot
    state root bit-identical to the sequential host composition; replay
    of a committed slot returns the identical result, flagged."""
    from eth_consensus_specs_tpu.serve.slot import SlotWorld

    world = SlotWorld(n_validators=N)
    for req, want in zip(slot_reqs, oracle):
        got, phases = world.execute(req, sp.prep_request(req))
        _assert_result_parity(got, want)
        assert set(phases) >= {"slot.verify", "slot.aggregate", "slot.reroot"}
    replayed, _ = world.execute(slot_reqs[0])
    assert replayed.replayed and replayed.state_root == oracle[0].state_root


@pytest.mark.slow
def test_device_death_degrades_the_whole_slot_atomically(slot_reqs, oracle):
    """Injected device failure at either site degrades the WHOLE slot to
    the host fold bit-identically — never a half-applied slot; one
    transient reroot failure retries on device and still matches."""
    from eth_consensus_specs_tpu.serve.slot import SlotWorld

    world = SlotWorld(n_validators=N)
    with fault.injected("slot.verify:raise:times=inf"):
        got, _ = world.execute(slot_reqs[0], sp.prep_request(slot_reqs[0]))
    _assert_result_parity(got, oracle[0])
    with fault.injected("slot.reroot:raise"):
        got, _ = world.execute(slot_reqs[1], sp.prep_request(slot_reqs[1]))
    _assert_result_parity(got, oracle[1])
    with fault.injected("slot.reroot:raise:times=inf"):
        for req, want in zip(slot_reqs[2:], oracle[2:]):
            got, _ = world.execute(req, sp.prep_request(req))
            _assert_result_parity(got, want)


@pytest.mark.slow
def test_checkpoint_restore_replays_committed_slots(slot_reqs, oracle):
    """A fresh world restoring from the durable checkpoint resumes at
    the last committed slot: committed slots replay bit-identically from
    the dedup window, uncommitted slots apply with parity."""
    from eth_consensus_specs_tpu.serve.slot import SlotWorld

    d = tempfile.mkdtemp()
    try:
        w1 = SlotWorld(n_validators=N, ckpt_dir=d)
        for req in slot_reqs[:2]:
            w1.execute(req, sp.prep_request(req))
        w2 = SlotWorld(n_validators=N, ckpt_dir=d)
        w2.boot()
        assert w2.root == oracle[1].state_root
        rb, _ = w2.execute(slot_reqs[1])
        assert rb.replayed and rb.state_root == oracle[1].state_root
        got, _ = w2.execute(slot_reqs[2], sp.prep_request(slot_reqs[2]))
        _assert_result_parity(got, oracle[2])
    finally:
        shutil.rmtree(d)


@pytest.mark.slow
def test_service_tier_submit_slot_phases_and_warm_shapes(slot_reqs, oracle):
    """submit_slot through the VerifyService: parity, the three phase
    walls in the stage histograms, and ZERO new compiles when a warm
    shape repeats (the compile key is a pure function of the request)."""
    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.serve.config import ServeConfig
    from eth_consensus_specs_tpu.serve.service import VerifyService

    cfg = ServeConfig.from_env(max_batch=8, max_wait_ms=5, slot_validators=N)
    svc = VerifyService(cfg)
    try:
        futs = [svc.submit_slot(r) for r in slot_reqs]
        got = [f.result(timeout=600) for f in futs]
        for d, w in zip(got, oracle):
            _assert_result_parity(d, w)
        for ph in ("slot.verify", "slot.aggregate", "slot.reroot"):
            h = obs.histogram(f"serve.stage_ms.{ph}")
            assert h is not None and h.count >= len(slot_reqs), ph
        assert svc.stats()["slot"]["slots"] >= len(slot_reqs)
        # warm shape: an identical-capacity NEW slot compiles nothing
        compiles = obs.snapshot()["counters"].get("serve.compiles", 0)
        again = make_req(9, boundary=False)
        got2 = svc.submit_slot(again).result(timeout=600)
        assert not got2.replayed
        assert obs.snapshot()["counters"].get("serve.compiles", 0) == compiles
    finally:
        svc.close()


@pytest.mark.slow
def test_mesh_and_single_device_worlds_agree(slot_reqs, oracle):
    """chips=1 vs chips=8 dispatch meshes produce bit-identical slot
    results — the mesh only widens the verify/aggregate legs."""
    from eth_consensus_specs_tpu.parallel.mesh_ops import serve_mesh
    from eth_consensus_specs_tpu.serve.slot import SlotWorld

    mesh = serve_mesh()
    if mesh is None:
        pytest.skip("needs >= 2 devices (conftest forces 8 on CPU)")
    w_single = SlotWorld(n_validators=N)
    w_mesh = SlotWorld(n_validators=N)
    for req, want in zip(slot_reqs[:2], oracle[:2]):
        a, _ = w_single.execute(req, sp.prep_request(req), mesh=None)
        b, _ = w_mesh.execute(req, sp.prep_request(req), mesh=mesh)
        _assert_result_parity(a, want)
        _assert_result_parity(b, want)
