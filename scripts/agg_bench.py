"""Million-validator aggregation-pipeline bench — one full simulated slot.

Direct mode: build an N-validator registry (``--subnets`` attestation
subnets x contiguous committees, synthetic BLS keys: validator i holds
sk = i+1, so signatures/pubkeys build incrementally without a per-
validator scalar multiply), then run the committee tree end to end —
per-subnet fan-in (tier 0), per-(subnet, root) partials (tier 1), the
global aggregate per attestation data root (tier 2) — and finally
VERIFY what was just built through the batched RLC path, with the
per-subnet partials fed to the ``verify_many`` bisection so injected
invalid committees are isolated to their (subnet, root).

Gates (direct mode):

  * bit parity vs the host oracle (``agg_tree.aggregate_slot_host``,
    the ``crypto/signature`` fold) at EVERY tier — committee, subnet
    partial, global (Points, bytes, and participation bits). A run
    that fails parity REFUSES to report throughput at all;
  * verification truth: clean roots verify True, roots holding an
    injected invalid committee verify False, and the bisection
    isolates exactly the injected (subnet, root) set;
  * zero cold compiles after the warmup pass (the warm slot run pays
    every (items, lanes[, mesh]) bucket compile; the timed reps must
    hit the jit cache only);
  * mesh parity (``--chips N``): the sharded slot's every tier
    bit-identical to the chips=1 dispatch — the chips=1-vs-N gate the
    acceptance demands.

Primary metric: **attestations aggregated + verified per second** at
registry scale (``agg.attestations_agg_per_s`` in the report's ``agg``
section).

Replicated mode (``--replicas R [--chaos]``, the agg-smoke CI job):
the committee fan-in submitted as ``aggregate`` ops through the
replicated front door (serve/frontdoor.py) — each committee's
compressed member signatures are one request, resolving to the exact
bytes ``crypto.signature.aggregate`` returns. ``--chaos`` SIGKILLs one
replica mid-fan-in (the deterministic ``frontdoor.rpc:kill`` grammar);
gates: zero lost requests, byte parity vs the host oracle on every
committee, and zero cold compiles on every replica — including the
respawned replacement, which warms from the shippable artifact.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from prejax import force_virtual_chips  # noqa: E402

force_virtual_chips()

import numpy as np  # noqa: E402

from serve_bench import (  # noqa: E402
    _LOST,
    closed_loop,
    finish_report,
    wait_replicas_surveyed,
)

from eth_consensus_specs_tpu import obs  # noqa: E402
from eth_consensus_specs_tpu.crypto import signature as sig_mod  # noqa: E402
from eth_consensus_specs_tpu.crypto.curve import (  # noqa: E402
    g1_generator,
    g2_generator,
    g2_to_bytes,
)
from eth_consensus_specs_tpu.crypto.hash_to_curve import hash_to_g2  # noqa: E402
from eth_consensus_specs_tpu.obs import export  # noqa: E402
from eth_consensus_specs_tpu.ops import agg_tree  # noqa: E402
from eth_consensus_specs_tpu.serve import buckets as serve_buckets  # noqa: E402
from eth_consensus_specs_tpu.serve.config import ServeConfig  # noqa: E402


def build_registry(
    n_validators: int,
    subnets: int,
    committee: int,
    n_roots: int = 2,
    invalid: int = 0,
    drop: int = 17,
) -> tuple[list, list]:
    """Synthesize the registry: validator i holds sk = i+1, committees
    are contiguous index ranges, attestation data roots are assigned in
    contiguous committee blocks (so per-root signature chains build by
    INCREMENTAL point addition — one scalar multiply per block start,
    one add per validator, which is what makes a million-validator
    registry constructible in minutes instead of hours). Every
    ``drop``-th validator abstains (ragged lanes + participation bits);
    the first member of each of ``invalid`` evenly-spread committees
    signs garbage. Returns (attestations, expected_bad)."""
    n_committees = max(n_validators // committee, 1)
    roots = [bytes([r + 1]) * 32 for r in range(n_roots)]
    bad_committees = {
        (i * n_committees) // invalid for i in range(invalid)
    } if invalid else set()
    G1, G2 = g1_generator(), g2_generator()
    atts, expected_bad = [], set()
    pk_run = None  # (i+1) * G1, built incrementally
    sig_run, sig_root = None, None  # (i+1) * H(root), per root block
    h_cache = {r: hash_to_g2(r) for r in roots}
    for c in range(n_committees):
        root = roots[(c * n_roots) // n_committees]
        base = h_cache[root]
        a = c * committee
        if sig_root != root:
            sig_run, sig_root = base.mul(a + 1), root
            started = a
        pks, sigs, bits = [], [], []
        for j in range(committee):
            v = a + j
            pk_run = G1 if v == 0 else pk_run + G1
            if v > started:
                sig_run = sig_run + base
            absent = drop > 0 and (v % drop) == drop - 1
            bits.append(not absent)
            if absent:
                continue
            pks.append(pk_run)
            sigs.append(sig_run)
        if c in bad_committees and sigs:
            sigs[0] = sigs[0] + G2  # a wrong signature, still on-curve
            expected_bad.add((c % subnets, root))
        atts.append(
            agg_tree.CommitteeAttestation(
                subnet=c % subnets, root=root,
                pubkeys=tuple(pks), sigs=tuple(sigs), bits=tuple(bits),
            )
        )
    return atts, sorted(expected_bad)


def _tiers_equal(a, b) -> bool:
    """(slot_aggs, subnet_aggs) equality at every tier: Points, bytes,
    participation bits — the bit-parity the gates demand."""
    slot_a, subs_a = a
    slot_b, subs_b = b
    if len(slot_a) != len(slot_b) or len(subs_a) != len(subs_b):
        return False
    for x, y in zip(subs_a, subs_b):
        if (x.subnet, x.root) != (y.subnet, y.root):
            return False
        if x.sig != y.sig or x.pubkey != y.pubkey:
            return False
        if not np.array_equal(x.bits, y.bits):
            return False
    for x, y in zip(slot_a, slot_b):
        if x.root != y.root or x.sig_bytes != y.sig_bytes:
            return False
        if x.pubkey_bytes != y.pubkey_bytes or not np.array_equal(x.bits, y.bits):
            return False
    return True


def run_direct(args) -> None:
    import jax

    from eth_consensus_specs_tpu.parallel import mesh_ops

    export.maybe_serve_http()
    platform = jax.local_devices()[0].platform
    mesh = mesh_ops.serve_mesh(args.chips) if args.chips > 1 else None
    failures: list = []

    t0 = time.time()
    atts, expected_bad = build_registry(
        args.validators, args.subnets, args.committee,
        n_roots=args.roots, invalid=args.invalid,
    )
    build_s = time.time() - t0
    obs.gauge("agg.registry_validators", args.validators)
    n_sigs = sum(len(a.sigs) for a in atts)

    # host-oracle truth at every tier (native-bridge accelerated where
    # available; pure python otherwise — untimed either way)
    t0 = time.time()
    host_tiers = agg_tree.aggregate_slot_host(atts)
    host_s = time.time() - t0

    # warmup: the warm slot run pays every bucket compile (and, via
    # ETH_SPECS_SERVE_WARMUP/--warmup-out, records the shippable keys)
    t0 = time.time()
    warm_tiers = agg_tree.aggregate_slot(atts, mesh=mesh)
    warm_verdicts = agg_tree.verify_slot(warm_tiers[0], mesh=mesh)
    warm_bad = agg_tree.isolate_invalid_subnets(warm_tiers[1], mesh=mesh)
    warmup_s = time.time() - t0
    compiles_after_warmup = obs.snapshot()["counters"].get("serve.compiles", 0)

    parity = _tiers_equal(warm_tiers, host_tiers)
    if not parity:
        failures.append("TIER PARITY FAILED: device tiers != host oracle "
                        "(throughput withheld)")

    # verification truth: clean roots True, poisoned roots False, and
    # the bisection isolates exactly the injected (subnet, root) set
    bad_roots = {root for _, root in expected_bad}
    want_verdicts = [sa.root not in bad_roots for sa in warm_tiers[0]]
    if warm_verdicts != want_verdicts:
        failures.append(
            f"verification verdicts {warm_verdicts} != expected {want_verdicts}"
        )
    if sorted(warm_bad) != expected_bad:
        failures.append(
            f"bisection isolated {sorted(warm_bad)} != injected {expected_bad}"
        )

    # timed reps: aggregate + verify, best-of-N against the jit cache
    best = None
    for _ in range(args.reps):
        t0 = time.perf_counter()
        tiers = agg_tree.aggregate_slot(atts, mesh=mesh)
        verdicts = agg_tree.verify_slot(tiers[0], mesh=mesh)
        if expected_bad:
            agg_tree.isolate_invalid_subnets(tiers[1], mesh=mesh)
        wall = time.perf_counter() - t0
        best = wall if best is None else min(best, wall)
        parity = parity and _tiers_equal(tiers, host_tiers)
        if verdicts != want_verdicts:
            failures.append("timed-rep verification verdicts diverged")
    if not parity and "TIER PARITY FAILED" not in "".join(failures):
        failures.append("TIER PARITY FAILED on a timed rep (throughput withheld)")

    # mesh parity: the chips=1-vs-N gate (single-device recompute)
    mesh_section = None
    if mesh is not None:
        single = agg_tree.aggregate_slot(atts, mesh=None)
        if not _tiers_equal(single, warm_tiers):
            failures.append("mesh parity FAILED: chips=1 tiers != sharded tiers")
        mesh_section = {
            "chips": args.chips,
            "shards": mesh_ops.shard_count(mesh),
            "signature": mesh_ops.mesh_signature(mesh),
            "parity": _tiers_equal(single, warm_tiers),
        }

    snap = obs.snapshot()
    counters = snap["counters"]
    extra = counters.get("serve.compiles", 0) - compiles_after_warmup
    if extra > 0:
        failures.append(f"{extra} compiles AFTER the warmup slot "
                        "(a shape escaped the agg buckets)")
    obs.count("serve.compiles_after_warmup", max(extra, 0))
    if snap["watchdog"]["divergences"] != 0:
        failures.append(f"watchdog divergences: {snap['watchdog']}")

    agg_metrics = {}
    if parity:
        agg_metrics = {
            "attestations_agg_per_s": round(len(atts) / best, 2),
            "signatures_agg_per_s": round(n_sigs / best, 2),
            "slot_wall_s": round(best, 3),
        }
    report = {
        "mode": "agg-smoke" if args.smoke else "agg",
        "platform": platform,
        "validators": args.validators,
        "subnets": args.subnets,
        "committee": args.committee,
        "attestations": len(atts),
        "signatures": n_sigs,
        "roots": args.roots,
        "invalid_injected": len(expected_bad),
        "registry_build_s": round(build_s, 2),
        "host_oracle_s": round(host_s, 2),
        "warmup_s": round(warmup_s, 2),
        "parity": parity,
        "agg": agg_metrics,
        "mesh": mesh_section,
        "compiles": counters.get("serve.compiles", 0),
        "compiles_after_warmup": max(extra, 0),
        "compile_ms": snap["histograms"].get("agg.compile_ms", {}),
    }
    if args.warmup_out:
        report["warmup_artifact"] = args.warmup_out
        report["warmup_keys"] = serve_buckets.write_warmup(args.warmup_out)
    snap = obs.snapshot()
    finish_report(report, failures, args.out, "agg_bench.failure", snap)


def run_replicated(args) -> None:
    """The --replicas path: the committee fan-in as ``aggregate`` ops
    through a supervised replica fleet, optionally with a deterministic
    mid-fan-in SIGKILL."""
    from eth_consensus_specs_tpu.serve.config import FrontDoorConfig
    from eth_consensus_specs_tpu.serve.frontdoor import FrontDoor

    export.maybe_serve_http()
    out_dir = os.path.dirname(os.path.abspath(args.out)) or "."
    pm_dir = os.environ.get("ETH_SPECS_OBS_POSTMORTEM_DIR")
    if not pm_dir:
        pm_dir = os.path.join(out_dir, "postmortems")
        os.environ["ETH_SPECS_OBS_POSTMORTEM_DIR"] = pm_dir
    warmup_path = args.warmup_out or os.path.join(out_dir, "agg_warmup.jsonl")

    atts, _ = build_registry(
        args.validators, args.subnets, args.committee,
        n_roots=args.roots, invalid=0,
    )
    obs.gauge("agg.registry_validators", args.validators)
    sig_sets = [[g2_to_bytes(p) for p in a.sigs] for a in atts if a.sigs]
    # host-oracle truth per committee — the parent never touches the
    # device, so "zero cold compiles on every replica" stays honest
    direct = [sig_mod.aggregate(s) for s in sig_sets]
    # pipeline sanity on the host tiers rides along for free
    host_slot, _ = agg_tree.aggregate_slot_host(atts)
    for sa in host_slot:
        want = sig_mod.aggregate(
            [g2_to_bytes(p) for a in atts if bytes(a.root) == sa.root for p in a.sigs]
        )
        assert sa.sig_bytes == want, "host committee tree diverged from flat fold"

    # ONE flush shape: max_batch=1 makes every agg flush a single item
    # (the g2_agg item axis buckets pow2 of the LIVE flush size, so a
    # mixed-size flush stream would need one ~minute XLA:CPU scan-body
    # compile per pow2 — the budget here is chaos/parity/cold-compile
    # gates, not batching, which serve-smoke already covers)
    cfg = ServeConfig.from_env(max_batch=1, buckets=(1,))
    lane_bucket = serve_buckets.agg_lane_bucket(args.committee)
    warm_keys = [("g2_agg", 1, lane_bucket)]
    fault_spec = None
    if args.chaos:
        nth = max(len(sig_sets) // 8, 2)
        latch = os.path.join(out_dir, f"agg_kill_{os.getpid()}.latch")
        if os.path.exists(latch):
            os.unlink(latch)
        fault_spec = f"frontdoor.rpc:kill:nth={nth}:latch={latch}"

    fd = FrontDoor(
        replicas=args.replicas,
        config=cfg,
        fd_config=FrontDoorConfig.from_env(ready_timeout_s=900.0),
        warmup_path=warmup_path,
        warm_keys=warm_keys,
        replica_fault_spec=fault_spec,
        name="agg-fd",
    )
    load = [("agg", s) for s in sig_sets]
    wall_s, got, _lat = closed_loop(fd, load, args.submitters, result_timeout=600.0)
    wait_replicas_surveyed(fd)  # incl. a chaos respawn still booting
    replica_stats = fd.replica_stats()
    stats = fd.stats()
    fd.close()

    failures = []
    lost = sum(1 for r in got if r is _LOST)
    if lost:
        failures.append(f"{lost} aggregate requests lost (futures never resolved)")
    if got != direct:
        failures.append("AGG parity: replicated aggregates != host-oracle bytes")
    snap = obs.snapshot()
    counters = snap["counters"]
    replaced = counters.get("frontdoor.replicas_replaced", 0)
    if args.chaos and replaced < 1:
        failures.append("chaos run but frontdoor.replicas_replaced == 0 "
                        "(the kill never happened or was never healed)")
    cold = {
        i: s["compiles_after_ready"]
        for i, s in enumerate(replica_stats)
        if s is not None and s.get("compiles_after_ready")
    }
    if cold:
        failures.append(f"cold compiles after warmup on replicas: {cold}")
    obs.count("serve.compiles_after_warmup", sum(cold.values()))
    surveyed = sum(1 for s in replica_stats if s is not None)
    if surveyed < args.replicas:
        failures.append(
            f"only {surveyed}/{args.replicas} replicas answered a health probe"
        )
    if snap["watchdog"]["divergences"] != 0:
        failures.append(f"watchdog divergences: {snap['watchdog']}")

    report = {
        "mode": "agg-replicated-chaos" if args.chaos else "agg-replicated",
        "replicas": args.replicas,
        "submitters": args.submitters,
        "validators": args.validators,
        "attestations": len(sig_sets),
        "agg": {
            "attestations_agg_per_s": round(len(sig_sets) / wall_s, 2)
            if got == direct else None,
        },
        "lost": lost,
        "replicas_replaced": replaced,
        "failovers": stats["failovers"],
        "hedges": stats["hedges"],
        "replica_stats": replica_stats,
        "warmup_artifact": warmup_path,
        "warmup_keys": len(serve_buckets.load_warmup(warmup_path)),
    }
    snap = obs.snapshot()
    finish_report(report, failures, args.out, "agg_bench.replicated_failure", snap)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small-registry CI run (same hard gates)")
    ap.add_argument("--validators", type=int, default=1 << 20,
                    help="registry size (default 1Mi — the acceptance scale)")
    ap.add_argument("--subnets", type=int, default=agg_tree.subnet_count())
    ap.add_argument("--committee", type=int, default=256,
                    help="validators per committee")
    ap.add_argument("--roots", type=int, default=2,
                    help="distinct attestation data roots per slot")
    ap.add_argument("--invalid", type=int, default=2,
                    help="committees injected with a wrong member signature")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--submitters", type=int, default=16)
    ap.add_argument("--chips", type=int,
                    default=int(os.environ.get("ETH_SPECS_SERVE_CHIPS", "0") or 0))
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the fan-in through an R-replica front door")
    ap.add_argument("--chaos", action="store_true",
                    help="with --replicas: SIGKILL one replica mid-fan-in")
    ap.add_argument("--out", default="BENCH_AGG.json")
    ap.add_argument("--warmup-out", default=None,
                    help="write the shippable warmup artifact here")
    args = ap.parse_args()
    if args.smoke:
        args.validators = min(args.validators, 2048)
        args.subnets = min(args.subnets, 8)
        args.committee = min(args.committee, 4)
        args.invalid = min(args.invalid, 1)
        args.reps = min(args.reps, 2)
        args.submitters = min(args.submitters, 8)
    if args.replicas > 0:
        run_replicated(args)
        return
    run_direct(args)


if __name__ == "__main__":
    main()
