"""The slot machine: mainnet-shaped whole-slot replay through a fleet.

Drives ``submit_slot`` (the whole-slot state-transition pipeline,
ops/slot_pipeline.py + serve/slot.py) end to end through a supervised
replica fleet and writes a JSON report (default BENCH_SLOT.json) whose
``slot`` section carries ``slots_per_s`` and the per-phase p99s.

The load is a deterministic, seeded schedule of mainnet-SHAPED slots:
ragged committees with realistic size spread, a sync aggregate, a
configurable invalid rate (bad aggregate signatures, bad blob proofs —
sparse, like a real network), a blob-count distribution (Poisson-ish,
capped at the DAS limit), and bursty arrivals (slots between epoch
boundaries land as one burst; a boundary slot is a sync point, exactly
the chain's own commutativity: participation ORs and balance credits
commute within an epoch window, the boundary does not).

Gates — all hard, every one REFUSES the throughput number on failure:

  * **bit parity** — every slot's verdicts/aggregates/epoch, every
    boundary slot's state root, and the FINAL root must equal the
    sequential host fold of the same schedule (``host_slot_fold``).
    A parity failure fails the run; no throughput is reported.
  * **zero lost slots** — every submitted slot resolves (Overloaded is
    flow control, honored with its ``retry_after_s`` hint, not loss).
  * **zero cold compiles after warmup** — the fleet boots from explicit
    slot warm keys (the LIVE ``buckets.slot_key`` over the schedule's
    request-derived capacities) plus the shippable warmup artifact;
    after the bench's warmup burst, NO replica may compile again —
    including (``--chaos``) the respawned owner, which must come up
    clean from the artifact its predecessor enriched.
  * **chaos** (``--chaos``) — the slot OWNER (replica 0, the single
    stateful member) is SIGKILLed mid-load. The supervisor must respawn
    it, the respawn must restore the durable checkpoint, and the load
    must finish with zero lost slots and bit parity intact: a committed
    slot re-submitted after the kill must come back ``replayed`` with
    its original (oracle-identical) root. Zero lost slots, zero
    double-applies, bit-identical restored state.

``--smoke`` shrinks the schedule for CI (the slot-smoke job in
checks.yml). Exit code 0 only if every gate passes.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from prejax import force_virtual_chips  # noqa: E402

force_virtual_chips()

import numpy as np  # noqa: E402

from eth_consensus_specs_tpu import obs  # noqa: E402
from eth_consensus_specs_tpu.obs import canary as canary_mod  # noqa: E402
from eth_consensus_specs_tpu.obs import export, timeline  # noqa: E402
from eth_consensus_specs_tpu.ops import slot_pipeline as sp  # noqa: E402
from eth_consensus_specs_tpu.serve import buckets as serve_buckets  # noqa: E402
from eth_consensus_specs_tpu.serve.config import ServeConfig  # noqa: E402
from eth_consensus_specs_tpu.utils import bls  # noqa: E402

from serve_bench import (  # noqa: E402
    finish_report,
    latency_histogram,
    wait_replicas_surveyed,
)

MAX_BLOBS = 6  # the DAS per-block sidecar cap the distribution respects


# ---------------------------------------------------------- the schedule --
#
# Deterministic from --seed: the parent builds the identical schedule
# for the oracle fold and the fleet load, and a re-run reproduces a
# failure exactly. Keys are vi+1000 (attesters) / i+2000 (sync) — the
# request carries its own pubkeys, so any fixed mapping works.


def _sign_att(members, root):
    sks = [1000 + int(vi) for vi in members]
    return bytes(bls.Aggregate([bls.Sign(sk, root) for sk in sks]))


def _blob_item(rng, bad=False):
    from eth_consensus_specs_tpu.crypto import kzg

    raw = rng.integers(0, 256, size=kzg.FIELD_ELEMENTS_PER_BLOB * 32, dtype=np.uint8)
    out = []
    for j in range(kzg.FIELD_ELEMENTS_PER_BLOB):
        v = int.from_bytes(raw[j * 32 : (j + 1) * 32].tobytes(), "big")
        out.append((v % kzg.BLS_MODULUS).to_bytes(32, "big"))
    blob = b"".join(out)
    c = kzg.blob_to_kzg_commitment(blob)
    p = kzg.compute_blob_kzg_proof(blob, c)
    if bad:
        blob = blob[:-1] + bytes([blob[-1] ^ 1])
    return (blob, bytes(c), bytes(p))


def build_schedule(args) -> list[sp.SlotRequest]:
    """Mainnet-shaped slots, scaled to the registry: each slot carries
    ``--committees`` ragged committees (sizes spread around n/8), a
    sync aggregate over a fixed-size random subset, sparse invalid
    items at ``--invalid-rate``, and a capped-Poisson blob count."""
    rng = np.random.default_rng(args.seed)
    n = args.validators
    c_lo = max(n // 16, 2)
    c_hi = max(n // 6, c_lo + 1)
    sync_n = min(max(n // 8, 4), 16)
    reqs = []
    for s in range(args.slots):
        atts = []
        for c in range(args.committees):
            size = int(rng.integers(c_lo, c_hi + 1))
            members = rng.choice(n, size=size, replace=False)
            bits = rng.random(size) < 0.9
            if not bits.any():
                bits[0] = True
            root = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
            signers = [int(vi) for vi, b in zip(members, bits) if b]
            sig = _sign_att(signers, root)
            if rng.random() < args.invalid_rate:
                sig = bytes(bls.Sign(9999, root))  # wrong key: bad aggregate
            atts.append(
                sp.SlotAttestation(
                    subnet=c % 8,
                    root=root,
                    committee=tuple(int(v) for v in members),
                    bits=tuple(bool(b) for b in bits),
                    pubkeys=tuple(bytes(bls.SkToPk(1000 + vi)) for vi in signers),
                    sig=sig,
                )
            )
        sync_idx = rng.choice(n, size=sync_n, replace=False)
        sync_msg = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        sync_sks = [2000 + i for i in range(sync_n)]
        sync_sig = bytes(bls.Aggregate([bls.Sign(sk, sync_msg) for sk in sync_sks]))
        if rng.random() < args.invalid_rate:
            sync_sig = bytes(bls.Sign(9999, sync_msg))
        n_blobs = min(int(rng.poisson(args.blob_rate)), MAX_BLOBS)
        blobs = tuple(
            _blob_item(rng, bad=rng.random() < args.invalid_rate)
            for _ in range(n_blobs)
        )
        reqs.append(
            sp.SlotRequest(
                slot=s,
                attestations=tuple(atts),
                sync_pubkeys=tuple(bytes(bls.SkToPk(sk)) for sk in sync_sks),
                sync_message=sync_msg,
                sync_sig=sync_sig,
                sync_indices=tuple(int(v) for v in sync_idx),
                blobs=blobs,
                epoch_boundary=(s + 1) % args.slots_per_epoch == 0,
            )
        )
    return reqs


def run_oracle(args, reqs):
    """The sequential host fold of the whole schedule — the bit truth
    every gate compares against (the exact SlotWorld world recipe)."""
    import jax

    import __graft_entry__ as graft
    from eth_consensus_specs_tpu.ops.state_root import synthetic_static

    spec = sp.slot_spec()
    static = synthetic_static(spec, args.validators)
    cols, just = graft._example_altair_inputs(args.validators)
    cols, just = jax.device_put(cols), jax.device_put(just)
    epoch, results = 0, []
    for req in reqs:
        res, cols, just = sp.host_slot_fold(spec, static, cols, just, req, epoch)
        epoch = res.epoch
        results.append(res)
    return results


def slot_warm_keys(args, reqs) -> list[tuple]:
    """Explicit warm keys for the fleet boot: every ``slot_apply``
    bucket the schedule's request-derived capacities will hit (the LIVE
    key fn — router, dispatch, and warmup can never disagree), plus the
    blob-verification lane buckets the sidecar distribution needs."""
    from eth_consensus_specs_tpu.ops.state_root import forest_plan, synthetic_static

    _, meta = synthetic_static(sp.slot_spec(), args.validators)
    plan = forest_plan(meta)
    keys = {serve_buckets.slot_key(args.validators, 1, 1, plan)}
    blob_counts = set()
    for req in reqs:
        flags, rewards = sp.request_capacity(req)
        keys.add(serve_buckets.slot_key(args.validators, flags, rewards, plan))
        if req.blobs:
            blob_counts.add(len(req.blobs))
    for c in blob_counts:
        keys.add(serve_buckets.kzg_msm_key(c))
    return sorted(keys)


# -------------------------------------------------------------- the load --

_LOST = object()


def submit_with_retry(fd, req, timeout_s: float, deadline_s: float):
    """One slot through the front door, honoring typed sheds (and the
    owner-down window during a chaos respawn) until the deadline."""
    from eth_consensus_specs_tpu.serve.admission import Overloaded

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            return fd.submit_slot(req).result(timeout=timeout_s)
        except Overloaded as exc:
            time.sleep(min(max(exc.retry_after_s, 0.05), 1.0))
        except Exception:
            time.sleep(0.2)
    return _LOST


def run_bench(args) -> None:
    from eth_consensus_specs_tpu.serve.config import FrontDoorConfig
    from eth_consensus_specs_tpu.serve.frontdoor import FrontDoor

    out_dir = os.path.dirname(os.path.abspath(args.out)) or "."
    os.makedirs(out_dir, exist_ok=True)
    pm_dir = os.environ.get("ETH_SPECS_OBS_POSTMORTEM_DIR")
    if not pm_dir:
        pm_dir = os.path.join(out_dir, "postmortems")
        os.environ["ETH_SPECS_OBS_POSTMORTEM_DIR"] = pm_dir
    warmup_path = args.warmup_out or os.path.join(out_dir, "warmup_shapes.jsonl")
    ckpt_dir = args.ckpt_dir or os.path.join(out_dir, "slot_ckpt")
    os.makedirs(ckpt_dir, exist_ok=True)

    export.maybe_serve_http()
    # fleet timeline source: stream this process's events as JSONL next
    # to the report (replicas inherit the env at spawn and re-point to
    # sibling files), so every run leaves an assemblable trace — the
    # autopsy epilogue and the CI Perfetto artifact both read it
    if not os.environ.get("ETH_SPECS_OBS_JSONL"):
        jsonl = os.path.splitext(os.path.abspath(args.out))[0] + ".events.jsonl"
        os.environ["ETH_SPECS_OBS_JSONL"] = jsonl
        obs.get_registry().configure_jsonl(jsonl)
    print(f"slot-machine: building {args.slots}-slot schedule "
          f"(n={args.validators}, invalid={args.invalid_rate}, "
          f"blobs~{args.blob_rate})", flush=True)
    reqs = build_schedule(args)
    oracle = run_oracle(args, reqs)

    cfg = ServeConfig.from_env(
        max_batch=8,
        max_wait_ms=5,
        slot_validators=args.validators,
        slot_ckpt_dir=ckpt_dir,
    )
    # continuous telemetry plane: structural detectors only (bench load
    # is not organic traffic), and a generous completion-stall horizon —
    # a single slot apply is legitimately seconds long on CPU, so the
    # default 15×200ms window would page on healthy full-scale runs
    os.environ.setdefault("ETH_SPECS_ANOM_DETECTORS", "structural")
    os.environ.setdefault("ETH_SPECS_ANOM_STALL_WINDOWS", "150")
    fd_cfg = FrontDoorConfig.from_env()
    if args.canary_ms > 0 and fd_cfg.canary_interval_ms <= 0:
        fd_cfg = dataclasses.replace(
            fd_cfg, canary_interval_ms=float(args.canary_ms))
    warm = slot_warm_keys(args, reqs)
    if fd_cfg.canary_interval_ms > 0:
        # canary compile shapes (flush-group size 1): the canary stream
        # rides the slot fleet's stateless verbs and must not cold-compile
        warm = sorted(set(warm) | set(canary_mod.warm_keys()))
    fd = FrontDoor(
        replicas=args.replicas,
        config=cfg,
        fd_config=fd_cfg,
        warmup_path=warmup_path,
        warm_keys=warm,
        name="slot-fd",
    )
    failures: list[str] = []
    try:
        _run_load(args, fd, reqs, oracle, failures, warmup_path, pm_dir)
    finally:
        fd.close()


def _windows(reqs):
    """Epoch windows: [commuting slots..., boundary sync point]."""
    win: list = []
    for req in reqs:
        win.append(req)
        if req.epoch_boundary:
            yield win
            win = []
    if win:
        yield win


def _check_slot(got, want, failures, gate_root: bool):
    if got is _LOST:
        failures.append(f"slot {want.slot}: LOST (never resolved)")
        return
    for field in ("att_verdicts", "sync_verdict", "blob_verdicts",
                  "subnet_aggregates", "epoch"):
        if getattr(got, field) != getattr(want, field):
            failures.append(
                f"slot {want.slot} parity: {field} diverged from the host fold"
            )
    if gate_root and got.state_root != want.state_root:
        failures.append(
            f"slot {want.slot} parity: root {got.state_root.hex()[:16]} != "
            f"host fold {want.state_root.hex()[:16]}"
        )


def _owner_compiles(fd) -> int:
    stats = fd.replica_stats()
    s = stats[0] if stats else None
    return int(s.get("compiles", 0)) if s else -1


def _run_load(args, fd, reqs, oracle, failures, warmup_path, pm_dir):
    want_by_slot = {r.slot: w for r, w in zip(reqs, oracle)}
    windows = list(_windows(reqs))

    # warmup burst: the first window, counted separately — after it, the
    # cold-compile gate arms (boot warm keys cover slot_apply/kzg; the
    # verify leg's bisection shapes surface here at the latest)
    latencies: list[float] = []
    results: dict[int, object] = {}
    t_start = time.perf_counter()
    chaos_done = not args.chaos
    killed_at = None
    recovery_s = None
    for wi, window in enumerate(windows):
        if wi == 1:
            wait_replicas_surveyed(fd)
            warm_compiles = _owner_compiles(fd)
        if not chaos_done and wi == max(len(windows) // 2, 1):
            # mid-load chaos: SIGKILL the OWNER — the single stateful
            # replica; the fleet has no failover for slots, only a
            # respawn-restore, which is exactly the contract under test
            proc = fd._procs[0]
            if proc is not None and proc.pid:
                print(f"chaos: SIGKILL slot owner pid={proc.pid}", flush=True)
                os.kill(proc.pid, signal.SIGKILL)
                killed_at = time.perf_counter()
            chaos_done = True
        body, boundary = window[:-1], window[-1]
        # bursty arrival: the window's slots land in bursts (they
        # commute: participation ORs + balance credits), then the
        # boundary slot is the sync point the roots are gated at
        gate_each_root = args.burst <= 1
        pending = list(body)
        while pending:
            burst, pending = pending[:args.burst], pending[args.burst:]
            t0 = time.perf_counter()
            got = [
                submit_with_retry(fd, r, args.timeout_s, args.deadline_s)
                for r in burst
            ]
            latencies.extend([(time.perf_counter() - t0) / max(len(burst), 1)] * len(burst))
            for r, g in zip(burst, got):
                results[r.slot] = g
                _check_slot(g, want_by_slot[r.slot], failures, gate_each_root)
        t0 = time.perf_counter()
        g = submit_with_retry(fd, boundary, args.timeout_s, args.deadline_s)
        latencies.append(time.perf_counter() - t0)
        results[boundary.slot] = g
        _check_slot(g, want_by_slot[boundary.slot], failures, gate_root=True)
        if killed_at is not None and recovery_s is None and g is not _LOST:
            recovery_s = time.perf_counter() - killed_at
    wall_s = time.perf_counter() - t_start

    lost = sum(1 for g in results.values() if g is _LOST)
    final_slot = reqs[-1].slot
    final = results.get(final_slot)
    if final is not _LOST and final is not None:
        if final.state_root != oracle[-1].state_root:
            failures.append("FINAL root diverged from the sequential host fold")

    # idempotent replay: a committed boundary slot re-submitted after the
    # load (post-chaos: through the RESTORED owner) must come back
    # replayed with its original, oracle-identical root — the
    # zero-double-apply proof
    replay_src = next((r for r in reqs if r.epoch_boundary), reqs[0])
    rep = submit_with_retry(fd, replay_src, args.timeout_s, args.deadline_s)
    if rep is _LOST:
        failures.append("replay probe lost")
    else:
        if not rep.replayed:
            failures.append("replay probe was re-applied, not replayed "
                            "(double-apply hazard)")
        if rep.state_root != want_by_slot[replay_src.slot].state_root:
            failures.append("replayed root != host fold root "
                            "(restored state diverged)")

    wait_replicas_surveyed(fd)
    replica_stats = fd.replica_stats()
    snap = obs.snapshot()
    counters = snap["counters"]

    if lost:
        failures.append(f"{lost} slots lost (zero-loss gate)")
    # zero cold compiles after the warmup window, fleet-wide: the
    # owner's compile counter must not move after window 0, siblings
    # must never compile after ready, and a chaos respawn must come up
    # clean from the enriched warmup artifact
    end_compiles = _owner_compiles(fd)
    owner_respawned = counters.get("frontdoor.replicas_replaced", 0) > 0
    if len(windows) > 1 and not owner_respawned:
        if end_compiles != warm_compiles:
            failures.append(
                f"cold compiles after warmup on the owner: "
                f"{warm_compiles} -> {end_compiles}"
            )
    cold = {
        i: s["compiles_after_ready"]
        for i, s in enumerate(replica_stats)
        if s is not None and i != 0 and s.get("compiles_after_ready")
    }
    if cold:
        failures.append(f"cold compiles after ready on siblings: {cold}")
    if owner_respawned:
        s0 = replica_stats[0] if replica_stats else None
        if s0 is None:
            failures.append("respawned owner never answered a health probe")
        elif s0.get("compiles_after_ready"):
            failures.append(
                f"respawned owner cold-compiled {s0['compiles_after_ready']} "
                "shapes after ready — the warmup artifact missed them"
            )
    if args.chaos and not owner_respawned:
        failures.append("chaos run but the owner was never replaced")

    if snap["watchdog"]["divergences"] != 0:
        failures.append(f"watchdog divergences: {snap['watchdog']}")

    # telemetry plane: canaries resolved bit-exactly through the slot
    # fleet's stateless verbs; structural detectors silent on a clean
    # run, and on a chaos run the owner kill is detected and attributed
    telemetry = fd.telemetry_report()
    can = telemetry.get("canary")
    if can is not None:
        if can.get("sent", 0) < 1:
            failures.append("no canaries sent through the slot front door")
        if can.get("parity_failures"):
            failures.append(
                f"{can['parity_failures']} canary parity failures — the fleet "
                "returned different bits than the host oracle")
    anom = telemetry.get("anomaly")
    if anom is not None:
        fires = dict(anom.get("fires") or {})
        if args.chaos:
            dead = [f for f in anom.get("fired", ())
                    if f.get("detector") == "dead_replica"]
            if not dead:
                failures.append("chaos run but the dead_replica detector "
                                "never fired on the owner kill")
            elif dead[0].get("replica") != 0 or dead[0].get("stage") != "recovery":
                failures.append(
                    f"dead_replica fired without owner attribution: {dead[0]}")
            # the owner kill legitimately trips the death/probe/stall
            # detectors (slots have no failover — completions stop until
            # the respawn-restore finishes); anything else is a lie
            unexpected = {
                k: v for k, v in fires.items()
                if k not in ("dead_replica", "probe_stall", "completion_stall")
            }
        else:
            unexpected = fires
        if unexpected:
            failures.append(f"unexpected anomaly fires: {unexpected}")

    phases = {}
    for ph in ("verify", "aggregate", "reroot"):
        h = snap["histograms"].get(f"serve.stage_ms.slot.{ph}", {})
        phases[f"{ph}_p50_ms"] = h.get("p50")
        phases[f"{ph}_p99_ms"] = h.get("p99")
        if not h.get("count"):
            failures.append(
                f"serve.stage_ms.slot.{ph} is empty — the phase waterfall "
                "never reached the parent"
            )

    slot_section = {
        "slots": len(reqs),
        "lost": lost,
        "replayed_probe_ok": rep is not _LOST and getattr(rep, "replayed", False),
        **phases,
        "host_folds": counters.get("serve.degraded_items", 0),
    }
    # the parity gate REFUSES the throughput number: a wrong-root fleet
    # has no legitimate slots/sec
    if not failures:
        slot_section["slots_per_s"] = round(len(reqs) / wall_s, 3)
        slot_section["correctness_coupled"] = True
    report = {
        "mode": "slot-chaos" if args.chaos else "slot",
        "replicas": args.replicas,
        "validators": args.validators,
        "slots_per_epoch": args.slots_per_epoch,
        "invalid_rate": args.invalid_rate,
        "blob_rate": args.blob_rate,
        "burst": args.burst,
        "seed": args.seed,
        "wall_s": round(wall_s, 3),
        "recovery_s": round(recovery_s, 3) if recovery_s is not None else None,
        "replicas_replaced": counters.get("frontdoor.replicas_replaced", 0),
        "final_root": oracle[-1].state_root.hex(),
        "latency_hist": latency_histogram(latencies),
        "replica_stats": replica_stats,
        "warmup_artifact": warmup_path,
        "warmup_keys": len(serve_buckets.load_warmup(warmup_path)),
        "slot": slot_section,
        "telemetry": telemetry,
    }
    # slot autopsy: the worst slot's critical path, from the fleet's
    # own JSONL streams under corrected clocks. On a chaos run the
    # attribution coverage GATES — a respawn whose outage doesn't land
    # in named stages means the recovery accounting broke
    jsonl = os.environ.get("ETH_SPECS_OBS_JSONL")
    if jsonl:
        autop = None
        try:
            autop = timeline.Timeline.from_path(jsonl).autopsy()
        except Exception as exc:  # noqa: BLE001 — diagnose, don't crash the bench
            failures.append(f"slot autopsy crashed: {exc!r}")
        if autop is not None:
            report["autopsy"] = autop
            print(timeline.render_autopsy(autop), flush=True)
            if args.chaos and autop["coverage"] < 0.95:
                failures.append(
                    f"autopsy attribution coverage {autop['coverage']:.3f} "
                    f"< 0.95 on the chaos run (slot {autop['slot']})"
                )
        elif args.chaos:
            failures.append("chaos run produced no autopsy (no slot "
                            "request events in the JSONL streams)")
    finish_report(report, failures, args.out, "slot_bench.failure", snap)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--validators", type=int, default=256)
    ap.add_argument("--committees", type=int, default=4)
    ap.add_argument("--slots-per-epoch", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--invalid-rate", type=float, default=0.1)
    ap.add_argument("--blob-rate", type=float, default=0.75,
                    help="mean of the capped-Poisson blob-count distribution")
    ap.add_argument("--burst", type=int, default=2,
                    help="slots per arrival burst within an epoch window; "
                    "1 additionally gates EVERY slot's root (strict order)")
    ap.add_argument("--chaos", action="store_true",
                    help="SIGKILL the slot owner mid-load")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink everything for CI")
    ap.add_argument("--seed", type=int, default=20260806)
    ap.add_argument("--timeout-s", type=float, default=300.0)
    ap.add_argument("--deadline-s", type=float, default=600.0,
                    help="per-slot overall deadline incl. retries/respawn")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--out", default="BENCH_SLOT.json")
    ap.add_argument("--warmup-out", default="")
    ap.add_argument("--canary-ms", type=float, default=250.0,
                    help="known-answer canary interval in ms through the "
                         "fleet's stateless verbs (0 disables)")
    args = ap.parse_args()
    if args.smoke:
        args.slots = min(args.slots, 10)
        args.validators = min(args.validators, 64)
        args.committees = min(args.committees, 3)
        args.slots_per_epoch = min(args.slots_per_epoch, 5)
    args.validators = max(args.validators, 32)
    args.slots_per_epoch = max(args.slots_per_epoch, 2)
    run_bench(args)


if __name__ == "__main__":
    main()
