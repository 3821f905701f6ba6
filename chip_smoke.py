#!/usr/bin/env python3
"""The quickest proof that the served slot path still starts on the chip.

One process imports the package, starts ONE in-process ``VerifyService`` at
a 2^20-validator registry under the mainnet preset and drives it the way a
user would: boot the resident slot world, two stateless verbs, then three
consecutive mainnet-shaped slots (64 attestations of 512-member committees,
a 512-key sync aggregate, 6 full-size blobs, one invalid attestation, one
invalid blob, the third slot an epoch boundary). Every answer is compared
with the host oracle the service's own degrade leg would have used, and the
run fails if any request was in fact answered from the host.

    python chip_smoke.py [--seed N]        one chip; what the driver runs
    python chip_smoke.py --chips 4         the sharded paths only, four chips

Prints one JSON line a phase with its wall seconds (which include each
kernel's first compile and are no measurement of anything), and as the last
line ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Exits non-zero, printing no such line, when JAX finds no TPU or any check
fails: nothing here catches an error and carries on.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (utils/cache.py). The sizes come from MAINNET
below; ``main()`` takes another ``Sizes`` only so that the tests can
rehearse the phases on the CPU at a tiny one.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time
from typing import NamedTuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


class Sizes(NamedTuple):
    validators: int  # registry size of the resident slot world
    committees: int  # attestations a slot
    committee_size: int  # members a committee
    sync_size: int  # keys in the sync aggregate
    blobs: int  # blob sidecars a slot (full size, always)
    htr_trees: int  # hash_tree_root requests in the stateless flush
    htr_depth: int  # their subtree depth


# 2^20 validators / 32 slots / 64 committees = 512 members; Deneb's
# MAX_BLOBS_PER_BLOCK = 6; SYNC_COMMITTEE_SIZE = 512. The stateless trees are
# the size of the registry's balance list (2^20 u64 = 2^18 chunks).
MAINNET = Sizes(1 << 20, 64, 512, 512, 6, 4, 18)
SLOTS = 3
# --chips 4: enough trees x chunks to clear buckets.mesh_dispatch_worthwhile,
# and the README's sharded-state shapes for the epoch + tree step
MESH_TREES, MESH_TREE_DEPTH = 8, 12
MESH_VALIDATORS, MESH_STEP_DEPTH = 1 << 20, 21


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, t0: float, **fields) -> None:
    print(
        json.dumps({"phase": phase, "seconds": round(time.perf_counter() - t0, 3), **fields}),
        flush=True,
    )


# ----------------------------------------------------------------- device --


def phase_device(chips: int) -> dict:
    """TPU or nothing: no platform is defaulted anywhere on this path."""
    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    check(dev["platform"] == "tpu", f"no accelerator: JAX found {dev}")
    check(dev["count"] == chips, f"asked for {chips} chip(s), JAX found {dev}")

    from eth_consensus_specs_tpu import native
    from eth_consensus_specs_tpu.obs import xprof
    from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

    # a C core that cannot be built would leave pure Python, some 60x
    # slower: at 512-key committees that is an hour, not a slower run
    cores = {"sha": native.get_lib() is not None, "bls": native.get_bls_lib() is not None}
    check(all(cores.values()), f"C cores did not build/load: {cores}")
    xprof.install_compile_listener()
    emit("device", t0, **dev, jax=jax.__version__, cache_dir=enable_persistent_cache(),
         c_cores=cores)
    return dev


def compile_summary() -> dict:
    """What XLA really compiled in this process, from the program's own
    listener (obs/xprof): the serve layer counts first sightings of a shape
    key, which a warm persistent cache turns into reads. JAX's
    backend-compile event spans the cache lookup, so a hit shows there with
    the seconds it took to read and load the executable; the reads are
    reported beside it. Sums and counts are the histograms' exact ones
    (`cache_hits` counts the reads); the counts over a second come from the
    ring's `xla.compile` events, which holds the last 10,000 events, many
    times what this script raises. With `ETH_SPECS_OBS=0` the program
    records nothing and every number here reads 0."""
    from eth_consensus_specs_tpu import obs

    hists = obs.snapshot()["histograms"]
    compiles = [h for name, h in hists.items() if name.startswith("xla.compile_ms.")]
    reads = [h for name, h in hists.items() if name.startswith("xla.cache_read_ms.")]
    events = [e for e in obs.get_registry().events if e["kind"] == "xla.compile"]
    return {
        "xla_compiles": sum(h["count"] for h in compiles),
        "xla_compile_s": round(sum(h["sum"] for h in compiles) / 1e3, 1),
        "xla_compiles_over_1s": sum(1 for e in events if e["ms"] > 1e3),
        "cache_hits": sum(h["count"] for h in reads),
        "cache_read_s": round(sum(h["sum"] for h in reads) / 1e3, 1),
        "cache_reads_over_1s": sum(1 for e in events if e["cache_read_ms"] > 1e3),
    }


# ------------------------------------------------------------------- boot --


class HostWorld(NamedTuple):
    """The slot world's deterministic recipe (serve/slot.py), built again
    here: what the host oracles fold over, and its root by the host."""

    spec: object
    static: tuple  # (StateRootArrays, StateRootMeta)
    cols: object
    just: object
    root: bytes


def host_world(sizes: Sizes) -> HostWorld:
    import jax
    import numpy as np

    import __graft_entry__ as graft
    from eth_consensus_specs_tpu.ops.slot_pipeline import _root_bytes, slot_spec
    from eth_consensus_specs_tpu.ops.state_root import (
        post_epoch_state_root_host,
        synthetic_static,
    )

    spec = slot_spec()
    cols, just = graft._example_altair_inputs(sizes.validators)
    arrays, meta = static = synthetic_static(spec, sizes.validators)
    root = _root_bytes(
        post_epoch_state_root_host(
            arrays, meta,
            np.asarray(cols.balance), np.asarray(cols.effective_balance),
            np.asarray(cols.inactivity_scores),
            jax.tree_util.tree_map(np.asarray, just),
        )
    )
    return HostWorld(spec, static, cols, just, root)


def phase_boot(svc, sizes: Sizes, world_h: HostWorld) -> None:
    t0 = time.perf_counter()
    from eth_consensus_specs_tpu.ops.slot_pipeline import SLOT_SPEC

    world = svc.slot_world()
    world.boot()  # cold ingest, forest built on the device, prewarm
    want = world_h.root
    check(world.root == want, f"boot root {world.root.hex()} != host recompute {want.hex()}")
    check(world.status()["lineage"]["verdict"] == "cold", "boot was not a cold ingest")
    emit("boot", t0, validators=sizes.validators, fork=SLOT_SPEC[0], preset=SLOT_SPEC[1],
         root=want.hex(), resident_bytes=sum(int(a.nbytes) for a in world.resident_arrays()))


# -------------------------------------------------------------- stateless --


def phase_stateless(svc, sizes: Sizes, world_h: HostWorld, seed: int) -> None:
    """The two verbs that need no limb kernel, through the same service;
    answers equal to the oracles of its degrade leg, called directly."""
    t0 = time.perf_counter()
    import numpy as np

    from eth_consensus_specs_tpu.obs.watchdog import host_tree_root_words
    from eth_consensus_specs_tpu.ops.merkle import _chunks_to_words
    from eth_consensus_specs_tpu.ops.slot_pipeline import _root_bytes

    rng = np.random.default_rng(seed)
    trees = [
        rng.integers(0, 256, (1 << sizes.htr_depth, 32), dtype=np.uint8)
        for _ in range(sizes.htr_trees)
    ]
    futs = [svc.submit_hash_tree_root(t) for t in trees]
    got = [f.result(timeout=1100) for f in futs]
    want = [host_tree_root_words(_chunks_to_words(t, 1 << sizes.htr_depth)) for t in trees]
    check(got == want, "submit_hash_tree_root differs from the host tree")

    (arrays, meta), cols = world_h.static, world_h.cols
    root = _root_bytes(svc.submit_state_root(
        arrays, meta, cols.balance, cols.effective_balance, cols.inactivity_scores,
        world_h.just,
    ).result(timeout=1100))
    check(root == world_h.root, f"submit_state_root {root.hex()} != host {world_h.root.hex()}")
    emit("stateless", t0, htr_trees=len(trees), htr_depth=sizes.htr_depth,
         state_root_validators=sizes.validators, state_root=root.hex())


# ------------------------------------------------------------------ slots --


def _digest(*parts) -> bytes:
    return hashlib.sha256(repr(parts).encode()).digest()


def make_blobs(count: int, seed: int) -> list[tuple[bytes, bytes, bytes]]:
    """``count`` full-size (4,096 field elements) blobs with their KZG
    commitments and proofs, made on the host from the seed."""
    import numpy as np

    from eth_consensus_specs_tpu.crypto import kzg

    rng = np.random.default_rng([seed, 0xB10B])
    out = []
    for _ in range(count):
        # 31 random bytes a field element: canonical (< the modulus) by width
        raw = rng.integers(0, 256, (kzg.FIELD_ELEMENTS_PER_BLOB, 32), dtype=np.uint8)
        raw[:, 0] = 0
        blob = raw.tobytes()
        commitment = bytes(kzg.blob_to_kzg_commitment(blob))
        out.append((blob, commitment, bytes(kzg.compute_blob_kzg_proof(blob, commitment))))
    return out


def build_slots(sizes: Sizes, seed: int) -> list:
    """SLOTS consecutive slot requests from the seed. Each validator sits
    in at most one committee (an epoch's shuffling gives each one duty);
    an aggregate signature is ONE sign under the sum of the members'
    secret keys, not one sign a member."""
    import numpy as np

    from eth_consensus_specs_tpu.crypto.fields import R
    from eth_consensus_specs_tpu.ops.slot_pipeline import SlotAttestation, SlotRequest
    from eth_consensus_specs_tpu.utils import bls

    n = sizes.validators
    per_slot = sizes.committees * sizes.committee_size
    check(SLOTS * per_slot <= n, "registry too small for disjoint committees")
    rng = np.random.default_rng([seed, 0x5107])
    duty = rng.permutation(n)[: SLOTS * per_slot].reshape(
        SLOTS, sizes.committees, sizes.committee_size
    )
    base = 1_000_003 + (seed << 24)

    def sk(vi: int) -> int:
        return base + int(vi)

    def signed(members, message: bytes):
        pubkeys = tuple(bytes(bls.SkToPk(sk(vi))) for vi in members)
        return pubkeys, bytes(bls.Sign(sum(sk(vi) for vi in members) % R, message))

    blobs = make_blobs(sizes.blobs, seed)
    reqs = []
    for slot in range(SLOTS):
        atts = []
        for c in range(sizes.committees):
            committee = tuple(int(v) for v in duty[slot, c])
            bits = rng.random(sizes.committee_size) >= 0.01  # ~99% participation
            bits[0] = True
            root = _digest("attestation", seed, slot, c)
            pubkeys, sig = signed([v for v, b in zip(committee, bits) if b], root)
            if (slot, c) == (1, sizes.committees // 3):
                sig = bytes(bls.Sign(sk(n + 7), root))  # the invalid attestation
            # one aggregation group a slot: the cross-committee aggregate
            # (all committees of a slot folded into one signature)
            atts.append(SlotAttestation(
                subnet=0, root=root, committee=committee,
                bits=tuple(int(b) for b in bits), pubkeys=pubkeys, sig=sig,
            ))
        sync_members = [int(v) for v in rng.choice(n, sizes.sync_size, replace=False)]
        sync_msg = _digest("sync", seed, slot)
        sync_pubkeys, sync_sig = signed(sync_members, sync_msg)
        sidecars = list(blobs)
        if slot == SLOTS - 1:
            # the invalid blob: a well-formed proof that is another point
            blob, commitment, _ = sidecars[0]
            sidecars[0] = (blob, commitment, commitment)
        reqs.append(SlotRequest(
            slot=slot, attestations=tuple(atts), sync_pubkeys=sync_pubkeys,
            sync_message=sync_msg, sync_sig=sync_sig, sync_indices=tuple(sync_members),
            blobs=tuple(sidecars), epoch_boundary=slot == SLOTS - 1,
        ))
    return reqs


def phase_slots(svc, sizes: Sizes, world_h: HostWorld, seed: int) -> None:
    t0 = time.perf_counter()
    import jax

    from eth_consensus_specs_tpu.ops.slot_pipeline import host_slot_fold

    reqs = build_slots(sizes, seed)
    built_s = round(time.perf_counter() - t0, 3)
    spec, static = world_h.spec, world_h.static
    cols, just = jax.device_put(world_h.cols), jax.device_put(world_h.just)
    epoch, walls, refused = 0, [], {"attestations": 0, "blobs": 0}
    for req in reqs:
        t1 = time.perf_counter()
        got = svc.submit_slot(req).result(timeout=1100)
        walls.append(round(time.perf_counter() - t1, 3))
        want, cols, just = host_slot_fold(spec, static, cols, just, req, epoch)
        epoch = want.epoch
        for field in dataclasses.fields(want):
            check(getattr(got, field.name) == getattr(want, field.name),
                  f"slot {req.slot}: {field.name} differs from host_slot_fold")
        check(got.sync_verdict, f"slot {req.slot}: valid sync aggregate refused")
        refused["attestations"] += sum(not v for v in got.att_verdicts)
        refused["blobs"] += sum(not v for v in got.blob_verdicts)
    check(refused == {"attestations": 1, "blobs": 1}, f"invalid items refused: {refused}")
    check(epoch == 1, "the boundary slot did not advance the accounting epoch")
    emit("slots", t0, slots=len(reqs), attestations=sizes.committees,
         committee_size=sizes.committee_size, sync_keys=sizes.sync_size, blobs=sizes.blobs,
         refused=refused, build_s=built_s, slot_wall_s=walls,
         root=want.state_root.hex(), epoch=epoch)


# ------------------------------------------------------------ no_fallback --


def expected_compile_keys(sizes: Sizes, svc) -> set:
    """The serve compile keys the phases above may first-dispatch: one a
    family (scripts/tpu_compile_inventory.py compiles exactly these). The
    BLS legs add none: the slot world hands `verify_many` its keys as bytes
    and no registry, so the served routing (ops/bls_batch.py) sums the
    committees in the C core; a service that was handed its registry
    dispatches ("bls_keysum", items, lanes, keys) for a bucket that
    `precompile` has warmed (benchmark cell block_atts_128.verify)."""
    from eth_consensus_specs_tpu.ops.kzg_batch import N_BLOB
    from eth_consensus_specs_tpu.ops.slot_pipeline import slot_spec
    from eth_consensus_specs_tpu.ops.state_root import (
        forest_plan,
        state_root_compile_key,
        synthetic_meta,
    )
    from eth_consensus_specs_tpu.serve import buckets

    n = sizes.validators
    meta = synthetic_meta(slot_spec(), n)
    plan = forest_plan(meta)
    return {
        ("resident_root", n, int(plan.shards)),
        ("resident", "state_inc", n, 1, int(plan.cap_val), int(plan.cap_bal)),
        buckets.slot_key(n, sizes.committees * sizes.committee_size, sizes.sync_size, plan),
        buckets.merkle_many_key(sizes.htr_trees, sizes.htr_depth, svc.config.buckets),
        state_root_compile_key(meta),
        buckets.fr_fft_key(sizes.blobs, N_BLOB),
        buckets.kzg_msm_key(sizes.blobs),
        buckets.g2_agg_key(1, sizes.committees),
    }


def phase_no_fallback(svc, sizes: Sizes, counters_at_start: dict) -> None:
    """After the last request: nothing degraded, nothing was rebuilt, the
    world lives on the device, and each family compiled once. Counters
    are read against their values when the service started (all zero in
    a process of its own; the rehearsal shares its process with tests)."""
    t0 = time.perf_counter()
    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.serve import buckets

    counters = obs.snapshot()["counters"]
    bad = {
        k: v - counters_at_start.get(k, 0) for k, v in counters.items()
        if v != counters_at_start.get(k, 0)
        and (k == "serve.degraded_items" or k == "slot.forest_rebuilds"
             or k == "fault.degraded" or k.startswith("fault.degraded."))
    }
    check(not bad, f"answered from a fallback: {bad}")

    import jax

    platform = jax.devices()[0].platform
    stray = {
        str(d) for a in svc.slot_world().resident_arrays() for d in a.devices()
        if d.platform != platform
    }
    check(not stray, f"slot world arrays off the {platform} device: {stray}")

    seen, want = set(buckets.seen_shapes()), expected_compile_keys(sizes, svc)
    families = [k[0] for k in seen]
    twice = sorted({f for f in families if families.count(f) > 1})
    check(seen == want, "serve.compiles differs from the inventory: "
          f"unexpected {sorted(seen - want)}, missing {sorted(want - seen)}, "
          f"compiled twice {twice}")
    emit("no_fallback", t0, degraded=0, forest_rebuilds=0, resident_on=platform,
         serve_compiles=int(counters.get("serve.compiles", 0)
                            - counters_at_start.get("serve.compiles", 0)),
         families=sorted(families), **compile_summary())


# ------------------------------------------------------------- four chips --


def phase_mesh(seed: int, chips: int = 4, validators: int = MESH_VALIDATORS,
               step_depth: int = MESH_STEP_DEPTH) -> None:
    """What exists only across chips, and only programs that compile in
    about a minute: a flush of hash_tree_root requests through a
    mesh_chips=4 and a mesh_chips=1 service in this one process, and the
    sharded epoch + sharded tree step against the unsharded kernels."""
    t0 = time.perf_counter()
    import numpy as np

    import __graft_entry__ as graft
    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.obs.watchdog import host_tree_root_words
    from eth_consensus_specs_tpu.ops.merkle import _chunks_to_words
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets
    from eth_consensus_specs_tpu.serve.config import ServeConfig
    from eth_consensus_specs_tpu.serve.service import VerifyService

    check(buckets.mesh_dispatch_worthwhile(1 << MESH_TREE_DEPTH, MESH_TREES)
          and MESH_TREES >= mesh_ops.min_items(), "flush too small to shard")
    rng = np.random.default_rng([seed, 0x3E5])
    trees = [
        rng.integers(0, 256, (1 << MESH_TREE_DEPTH, 32), dtype=np.uint8)
        for _ in range(MESH_TREES)
    ]
    want = [host_tree_root_words(_chunks_to_words(t, 1 << MESH_TREE_DEPTH)) for t in trees]
    roots, sharded_dispatches = {}, {}
    for n in (chips, 1):
        before = obs.snapshot()["counters"].get("mesh.dispatches", 0)
        svc = VerifyService(
            ServeConfig(max_batch=MESH_TREES, max_wait_ms=250.0, mesh_chips=n),
            name=f"mesh{n}",
        )
        try:
            futs = [svc.submit_hash_tree_root(t) for t in trees]
            roots[n] = [f.result(timeout=1100) for f in futs]
        finally:
            svc.close()
        sharded_dispatches[n] = obs.snapshot()["counters"].get("mesh.dispatches", 0) - before
    check(roots[chips] == roots[1] == want, "sharded, unsharded and host roots differ")
    check(sharded_dispatches[chips] >= 1 and sharded_dispatches[1] == 0,
          f"mesh dispatches per service: {sharded_dispatches}")
    mesh = mesh_ops.serve_mesh(chips)
    check(mesh is not None and mesh.devices.size == chips, "serve mesh does not span the chips")
    signed = [k for k in buckets.seen_shapes() if mesh_ops.mesh_signature(mesh) in k]
    check(signed, "no compile key carries the mesh signature")
    emit("mesh_serve", t0, trees=MESH_TREES, depth=MESH_TREE_DEPTH,
         mesh=mesh_ops.mesh_signature(mesh), sharded_dispatches=sharded_dispatches[chips],
         keys=[list(k) for k in signed])

    t1 = time.perf_counter()
    emit("mesh_step", t1, **graft.multichip_step(chips, validators, step_depth))


# ------------------------------------------------------------------- main --


def run_phases(sizes: Sizes, seed: int) -> None:
    """Everything after the device check, in order, through one service."""
    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.serve.config import ServeConfig
    from eth_consensus_specs_tpu.serve.service import VerifyService

    counters_at_start = dict(obs.snapshot()["counters"])
    # a flush closes when the stateless trees are all in (one bucket, one
    # compile); a lone slot request waits out max_wait_ms and goes alone.
    # mesh_chips=1: this is the one-chip path wherever it is rehearsed
    svc = VerifyService(ServeConfig(
        slot_validators=sizes.validators, max_batch=sizes.htr_trees, max_wait_ms=250.0,
        mesh_chips=1,
    ))
    try:
        world_h = host_world(sizes)
        phase_boot(svc, sizes, world_h)
        phase_stateless(svc, sizes, world_h, seed)
        phase_slots(svc, sizes, world_h, seed)
        phase_no_fallback(svc, sizes, counters_at_start)
    finally:
        svc.close()


def main(argv: list[str] | None = None, sizes: Sizes = MAINNET) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of every generated input")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths, on a four-chip host")
    args = ap.parse_args(argv)
    dev = phase_device(args.chips)
    if args.chips == 1:
        run_phases(sizes, args.seed)
    else:
        phase_mesh(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
