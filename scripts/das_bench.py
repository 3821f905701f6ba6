"""Blob-scale DAS/KZG verification bench — the workload that re-earns
the quarantined ``das`` LKG section honestly.

Direct mode: synthesize ``--blobs`` full-size (4096-field-element)
blobs from sparse monomial polynomials — commitment and proof are then
K-lane MSMs over the monomial setup points instead of 4096-lane ones,
which is what makes blob-scale registry construction take seconds
instead of hours, while the VERIFIER still does the full 4096-point
work on every item — inject ``--invalid`` tampered proofs, and run the
device pipeline end to end: ONE batched inverse FFT (challenge
evaluation), ONE RLC-combined multi-MSM, one pairing check, with the
injected invalid blobs isolated via RLC bisection.

Gates (direct mode) — a run that fails ANY of them REFUSES to report
throughput at all:

  * per-item verdicts bit-identical to the ``crypto/kzg.py`` host
    oracle (clean blobs True, tampered blobs False, the bisection
    isolating exactly the injected set), and the device batch verdict
    equal to ``verify_blob_kzg_proof_batch`` on the clean subset;
  * zero cold compiles after the warmup pass (the warm flush pays
    every fr_fft / kzg bucket compile; timed reps hit the jit cache);
  * mesh parity (``--chips N``): the sharded flush's verdicts — and
    the isolated invalid set — bit-identical to the chips=1 dispatch;
  * zero watchdog divergences (the sampled host recompute agreed).

Primary metric: **blobs verified per second** (``das.blobs_per_s``;
``ffts_per_s`` rides along — one 4096-point inverse FFT row per blob).
The report's ``das`` section carries ``correctness_coupled: true``
exactly when the parity gates passed.

Replicated mode (``--replicas R [--chaos]``, the das-smoke CI job):
every blob rides a ``kzg`` op through the replicated front door.
``--chaos`` SIGKILLs one replica mid-flush AND corrupts two
``frontdoor.rpc`` frames (the deterministic fault grammar); gates:
zero lost requests, verdict parity vs the host oracle on every blob,
``frontdoor.replicas_replaced > 0``, corrupt frames detected (never
silently accepted), and zero cold compiles on every replica —
including the respawned replacement, which warms from the shippable
artifact.
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

from serve_bench import (  # noqa: E402
    _LOST,
    closed_loop,
    finish_report,
    wait_replicas_surveyed,
)

from eth_consensus_specs_tpu import obs  # noqa: E402
from eth_consensus_specs_tpu.crypto import kzg  # noqa: E402
from eth_consensus_specs_tpu.obs import export  # noqa: E402
from eth_consensus_specs_tpu.serve import buckets as serve_buckets  # noqa: E402
from eth_consensus_specs_tpu.serve.config import ServeConfig  # noqa: E402


def build_blobs(n: int, degree: int, invalid: int) -> tuple[list, set]:
    """n (blob, commitment, proof) triples (the shared sparse-monomial
    construction — test_infra/blob.py); ``invalid`` evenly spread items
    get a tampered (still on-curve, still subgroup) proof. Returns
    (items, expected_invalid_indices)."""
    from eth_consensus_specs_tpu.test_infra.blob import sparse_blob_triple

    bad = {(i * n) // invalid for i in range(invalid)} if invalid else set()
    return [
        sparse_blob_triple(i, degree=degree, tamper=i in bad) for i in range(n)
    ], bad


def run_direct(args) -> None:
    import jax

    from eth_consensus_specs_tpu.ops import kzg_batch
    from eth_consensus_specs_tpu.parallel import mesh_ops

    export.maybe_serve_http()
    platform = jax.local_devices()[0].platform
    mesh = mesh_ops.serve_mesh(args.chips) if args.chips > 1 else None
    failures: list = []

    t0 = time.time()
    items, expected_bad = build_blobs(args.blobs, args.degree, args.invalid)
    build_s = time.time() - t0
    obs.gauge("das.blobs", len(items))
    want = [i not in expected_bad for i in range(len(items))]
    clean = [it for i, it in enumerate(items) if i not in expected_bad]

    # host-oracle truth per item (pure crypto/kzg.py path — untimed)
    t0 = time.time()
    host = [kzg_batch.verify_blob_host(*it) for it in items]
    # zip(*[]) expands to zero args: an all-invalid run still exercises
    # the empty-batch contract (True by spec) instead of crashing
    host_batch = kzg.verify_blob_kzg_proof_batch(
        *(map(list, zip(*clean)) if clean else ([], [], []))
    )
    host_s = time.time() - t0
    if host != want:
        failures.append("host oracle disagrees with the injected-invalid plan "
                        "(bench construction broken)")
    if not host_batch:
        failures.append("host batch verifier rejected the clean subset")

    # warmup: pays every fr_fft / kzg bucket compile (and records the
    # shippable keys via ETH_SPECS_SERVE_WARMUP / --warmup-out). The
    # chips=1 parity recompute runs INSIDE the warmup window too — its
    # unsharded kernel compiles are warmup cost, not escaped shapes.
    t0 = time.time()
    obs.count("das.flushes", 1)
    warm = kzg_batch.verify_many_blobs(items, mesh=mesh)
    warm_batch = kzg_batch.verify_blob_kzg_proof_batch_device(
        *(map(list, zip(*clean)) if clean else ([], [], [])), mesh=mesh
    )
    single = kzg_batch.verify_many_blobs(items, mesh=None) if mesh is not None else None
    warmup_s = time.time() - t0
    compiles_after_warmup = obs.snapshot()["counters"].get("serve.compiles", 0)

    parity = warm == host and warm_batch == host_batch
    if warm != host:
        failures.append("PARITY FAILED: device verdicts != host oracle "
                        "(throughput withheld)")
    if warm_batch != host_batch:
        failures.append("PARITY FAILED: device batch verdict != host batch "
                        "(throughput withheld)")
    isolated = {i for i, v in enumerate(warm) if not v}
    if isolated != expected_bad:
        failures.append(
            f"bisection isolated {sorted(isolated)} != injected {sorted(expected_bad)}"
        )

    # timed reps: the all-valid flush (ONE FFT + ONE MSM + one pairing),
    # best-of-N against the jit cache
    best = None
    for _ in range(args.reps):
        obs.count("das.flushes", 1)
        t0 = time.perf_counter()
        verdicts = kzg_batch.verify_many_blobs(clean, mesh=mesh)
        wall = time.perf_counter() - t0
        best = wall if best is None else min(best, wall)
        if verdicts != [True] * len(clean):
            failures.append("timed-rep verdicts diverged on the clean flush")
            parity = False

    # mesh parity: the chips=1-vs-N gate (recomputed during warmup)
    mesh_section = None
    if mesh is not None:
        if single != warm:
            failures.append("mesh parity FAILED: chips=1 verdicts != sharded")
            parity = False
        mesh_section = {
            "chips": args.chips,
            "shards": mesh_ops.shard_count(mesh),
            "signature": mesh_ops.mesh_signature(mesh),
            "parity": single == warm,
        }

    snap = obs.snapshot()
    counters = snap["counters"]
    extra = counters.get("serve.compiles", 0) - compiles_after_warmup
    if extra > 0:
        failures.append(f"{extra} compiles AFTER the warmup flush "
                        "(a shape escaped the kzg/fr_fft buckets)")
    obs.count("serve.compiles_after_warmup", max(extra, 0))
    if snap["watchdog"]["divergences"] != 0:
        failures.append(f"watchdog divergences: {snap['watchdog']}")

    das_metrics = {}
    if parity and not failures:
        das_metrics = {
            "blobs_per_s": round(len(clean) / best, 2),
            "ffts_per_s": round(len(clean) / best, 2),
            "flush_wall_s": round(best, 4),
            "correctness_coupled": True,
        }
    report = {
        "mode": "das-smoke" if args.smoke else "das",
        "platform": platform,
        "blobs": len(items),
        "degree": args.degree,
        "invalid_injected": len(expected_bad),
        "registry_build_s": round(build_s, 2),
        "host_oracle_s": round(host_s, 2),
        "warmup_s": round(warmup_s, 2),
        "parity": parity,
        "das": das_metrics,
        "mesh": mesh_section,
        "compiles": counters.get("serve.compiles", 0),
        "compiles_after_warmup": max(extra, 0),
    }
    if args.warmup_out:
        report["warmup_artifact"] = args.warmup_out
        report["warmup_keys"] = serve_buckets.write_warmup(args.warmup_out)
    snap = obs.snapshot()
    finish_report(report, failures, args.out, "das_bench.failure", snap)


def run_replicated(args) -> None:
    """The --replicas path: every blob as a ``kzg`` op through a
    supervised replica fleet, optionally with a deterministic mid-flush
    SIGKILL plus wire corruption."""
    from eth_consensus_specs_tpu.serve.config import FrontDoorConfig
    from eth_consensus_specs_tpu.serve.frontdoor import FrontDoor

    export.maybe_serve_http()
    out_dir = os.path.dirname(os.path.abspath(args.out)) or "."
    pm_dir = os.environ.get("ETH_SPECS_OBS_POSTMORTEM_DIR")
    if not pm_dir:
        pm_dir = os.path.join(out_dir, "postmortems")
        os.environ["ETH_SPECS_OBS_POSTMORTEM_DIR"] = pm_dir
    warmup_path = args.warmup_out or os.path.join(out_dir, "das_warmup.jsonl")

    items, expected_bad = build_blobs(args.blobs, args.degree, args.invalid)
    obs.gauge("das.blobs", len(items))
    from eth_consensus_specs_tpu.ops.kzg_batch import verify_blob_host

    # host-oracle truth per blob — the parent never touches the device,
    # so "zero cold compiles on every replica" stays honest
    direct = [verify_blob_host(*it) for it in items]

    # ONE flush shape: max_batch=1 makes every kzg flush a single item
    # (the budget here is chaos/parity/cold-compile gates, not
    # batching, which direct mode covers) — so the warm keys are the
    # singleton fr_fft batch + the singleton RLC lane bucket
    cfg = ServeConfig.from_env(max_batch=1, buckets=(1,))
    warm_keys = [
        ("fr_fft", 1, kzg.FIELD_ELEMENTS_PER_BLOB),
        ("kzg", serve_buckets.kzg_lane_bucket(1)),
    ]
    fault_spec = None
    if args.chaos:
        nth = max(len(items) // 4, 2)
        latch = os.path.join(out_dir, f"das_kill_{os.getpid()}.latch")
        if os.path.exists(latch):
            os.unlink(latch)
        fault_spec = (
            f"frontdoor.rpc:kill:nth={nth}:latch={latch};"
            f"frontdoor.rpc:corrupt:nth=2:times=2"
        )

    fd = FrontDoor(
        replicas=args.replicas,
        config=cfg,
        fd_config=FrontDoorConfig.from_env(ready_timeout_s=900.0),
        warmup_path=warmup_path,
        warm_keys=warm_keys,
        replica_fault_spec=fault_spec,
        name="das-fd",
    )
    load = [("kzg", it) for it in items]
    wall_s, got, _lat = closed_loop(fd, load, args.submitters, result_timeout=600.0)
    # the cold-compile gate must survey EVERY replica — including a
    # chaos respawn whose boot (artifact replay = the kzg + fr_fft
    # compiles) can outlive a small flush on a slow box
    wait_replicas_surveyed(fd)
    replica_stats = fd.replica_stats()
    stats = fd.stats()
    fd.close()

    failures = []
    lost = sum(1 for r in got if r is _LOST)
    if lost:
        failures.append(f"{lost} kzg requests lost (futures never resolved)")
    if got != direct:
        failures.append("KZG parity: replicated verdicts != host-oracle bools")
    snap = obs.snapshot()
    counters = snap["counters"]
    replaced = counters.get("frontdoor.replicas_replaced", 0)
    if args.chaos and replaced < 1:
        failures.append("chaos run but frontdoor.replicas_replaced == 0 "
                        "(the kill never happened or was never healed)")
    if args.chaos and counters.get("frontdoor.corrupt_frames", 0) < 1:
        failures.append("chaos run but zero corrupt frames detected "
                        "(the corruption rule never fired or was silently "
                        "accepted)")
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
        "mode": "das-replicated-chaos" if args.chaos else "das-replicated",
        "replicas": args.replicas,
        "submitters": args.submitters,
        "blobs": len(items),
        "invalid_injected": len(expected_bad),
        "das": {
            "blobs_per_s": round(len(items) / wall_s, 2)
            if got == direct else None,
        },
        "lost": lost,
        "replicas_replaced": replaced,
        "failovers": stats["failovers"],
        "hedges": stats["hedges"],
        "corrupt_frames": counters.get("frontdoor.corrupt_frames", 0),
        "replica_stats": replica_stats,
        "warmup_artifact": warmup_path,
        "warmup_keys": len(serve_buckets.load_warmup(warmup_path)),
    }
    snap = obs.snapshot()
    finish_report(report, failures, args.out, "das_bench.replicated_failure", snap)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small-flush CI run (same hard gates)")
    ap.add_argument("--blobs", type=int, default=64,
                    help="blobs per flush (full 4096-element blobs)")
    ap.add_argument("--degree", type=int, default=8,
                    help="sparse monomial degree of the synthesized blobs "
                    "(construction cost only; the verifier always does the "
                    "full 4096-point work)")
    ap.add_argument("--invalid", type=int, default=2,
                    help="blobs injected with a tampered proof")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions, floored at 1 (the throughput "
                    "metric needs at least one measured flush)")
    ap.add_argument("--submitters", type=int, default=8)
    ap.add_argument("--chips", type=int,
                    default=int(os.environ.get("ETH_SPECS_SERVE_CHIPS", "0") or 0))
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the flush through an R-replica front door")
    ap.add_argument("--chaos", action="store_true",
                    help="with --replicas: SIGKILL one replica mid-flush and "
                    "corrupt frontdoor.rpc frames")
    ap.add_argument("--out", default="BENCH_DAS.json")
    ap.add_argument("--warmup-out", default=None,
                    help="write the shippable warmup artifact here")
    args = ap.parse_args()
    args.reps = max(args.reps, 1)
    if args.smoke:
        args.blobs = min(args.blobs, 8)
        args.invalid = min(args.invalid, 1)
        args.reps = min(args.reps, 2)
        args.submitters = min(args.submitters, 4)
    if args.replicas > 0:
        run_replicated(args)
        return
    run_direct(args)


if __name__ == "__main__":
    main()
