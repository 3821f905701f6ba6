"""CI recovery smoke for durable resident state (checks.yml `recovery-smoke`).

One resident replica behind the front door is SIGKILLed mid-advance by a
deterministic fault rule (``resident.checkpoint:kill`` — the chaos fires
at the checkpoint commit seam, after the chunk's epochs ran on device
but before a single byte of the commit lands, so the previous LATEST
must survive intact), and the durable-state contract is gated end to
end:

  * **zero lost requests** — the client retries every advance until
    acked; every in-flight RPC across the kill fails DETECTABLY
    (connection error or honest busy), never silently; the world
    converges on exactly the target epoch;
  * **the kill happened AND was healed** — frontdoor.replicas_replaced
    >= 1 and a frontdoor.replica_lost postmortem bundle on disk;
  * **restore-then-replay, not cold start** — the respawned replica's
    lineage verdict is ``restored`` and its final root is BIT-IDENTICAL
    to an uninterrupted in-process control run of the same
    deterministic world (the recovery parity gate of ops/snapshot.py);
  * **recovery is a first-class waterfall stage** —
    ``serve.stage_ms.recovery`` (death -> replacement ready) is
    non-empty in the parent's merged registry and carries the restore
    lineage in its frontdoor.replica_recovered event;
  * **honest busy while restoring** — every overloaded / restoring
    reply observed mid-boot carried ``retry_after_s > 0`` (the measured
    restore wall, never a blackhole);
  * **zero cold compiles after ready** on the replacement — the
    resident prewarm covered the epoch runner, the root gate, and the
    scrub kernel;
  * **a clean post-recovery scrub** — K salted subtrees re-hash against
    the restored parents with zero mismatches.

Exit code 0 on success; prints a one-line JSON summary; dumps a
postmortem bundle (flight recorder) when any gate fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_root(validators: int, epochs: int) -> bytes:
    """Uninterrupted in-process truth: the SAME deterministic world the
    replica builds (seeded columns + synthetic static), advanced
    ``epochs`` with no checkpoints. Replicas are spawned with fresh
    runtimes, so parent-side work cannot pre-warm them — the replica's
    zero-cold-compile gate stays honest."""
    import jax

    import __graft_entry__ as graft
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops.state_root import synthetic_static
    from eth_consensus_specs_tpu.parallel import resident

    spec = get_spec("altair", "minimal")
    cols, just = graft._example_altair_inputs(validators)
    static = synthetic_static(spec, validators)
    _, root, _ = resident.run_epochs_checkpointed(
        spec, jax.device_put(cols), jax.device_put(just), epochs, static=static
    )
    return root


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--validators", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=6, help="target epoch")
    ap.add_argument("--interval", type=int, default=2, help="checkpoint interval")
    ap.add_argument("--out", default="recovery_smoke.json")
    ap.add_argument("--timeout", type=float, default=600.0)
    args = ap.parse_args()

    sys.path.insert(0, REPO)

    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.obs import flight
    from eth_consensus_specs_tpu.serve.config import ServeConfig
    from eth_consensus_specs_tpu.serve.frontdoor import FrontDoor

    out_dir = os.path.dirname(os.path.abspath(args.out)) or "."
    pm_dir = os.environ.get("ETH_SPECS_OBS_POSTMORTEM_DIR")
    if not pm_dir:
        pm_dir = os.path.join(out_dir, "postmortems")
        os.environ["ETH_SPECS_OBS_POSTMORTEM_DIR"] = pm_dir

    t0 = time.perf_counter()
    ctl = control_root(args.validators, args.epochs)
    control_s = time.perf_counter() - t0

    base = tempfile.mkdtemp(prefix="recovery_smoke_")
    ckpt_dir = os.path.join(base, "ckpt")
    # hit 1 of the site is the boot checkpoint (establishes LATEST);
    # hit 2 is the first advance's commit -> SIGKILL mid-request. The
    # latch arbitrates ACROSS process lives: the respawned replica
    # reinstalls the same rule with fresh counters, but the latch file
    # already exists, so the replacement is never re-killed.
    latch = os.path.join(base, "kill.latch")
    fault_spec = f"resident.checkpoint:kill:nth=2:latch={latch}"
    cfg = ServeConfig.from_env(
        resident_ckpt_dir=ckpt_dir,
        resident_validators=args.validators,
        resident_ckpt_interval=args.interval,
        resident_restore="prefer",
    )

    t0 = time.perf_counter()
    fd = FrontDoor(
        replicas=1, config=cfg, replica_fault_spec=fault_spec, name="recovery-fd"
    )

    target = args.epochs
    issued = acked = detected = busy_seen = 0
    dishonest: list = []
    scrub_rep: dict | None = None
    final: dict | None = None
    deadline = time.monotonic() + args.timeout

    def backoff(reply: dict) -> None:
        nonlocal busy_seen
        busy_seen += 1
        ra = reply.get("retry_after_s")
        if not isinstance(ra, (int, float)) or ra <= 0:
            dishonest.append(reply)
        time.sleep(min(float(ra or 0.5), 2.0))

    while time.monotonic() < deadline:
        try:
            st = fd._rpc_admin(0, {"op": "resident.status"}, 30.0)
        except Exception:  # noqa: BLE001 — dead/respawning slot: detected, retried
            detected += 1
            time.sleep(0.5)
            continue
        if not st.get("ok"):
            time.sleep(0.5)
            continue
        if st.get("restoring"):
            backoff(st)
            continue
        epoch = int(st.get("epoch", 0))
        if epoch >= target:
            final = st
            break
        issued += 1
        try:
            r = fd._rpc_admin(
                0,
                {"op": "resident.epochs", "n": min(args.interval, target - epoch)},
                300.0,
            )
        except Exception:  # noqa: BLE001 — the kill lands HERE: the in-flight
            detected += 1  # advance dies with its replica; retried, never silent
            time.sleep(0.5)
            continue
        if r.get("ok"):
            acked += 1
        elif r.get("err") == "overloaded":
            backoff(r)
        else:
            raise SystemExit(f"unexpected resident.epochs reply: {r}")

    # post-recovery scrub: K salted subtrees vs the restored parents
    if final is not None:
        try:
            scrub_rep = fd._rpc_admin(0, {"op": "resident.scrub"}, 120.0)
        except Exception:  # noqa: BLE001 — gated below as a failure
            scrub_rep = None

    # the replacement's OWN health stats (the supervisor clears the dead
    # predecessor's snapshot on death — never read its numbers)
    surveyed_by = time.monotonic() + 120.0
    while time.monotonic() < surveyed_by:
        stats = fd.replica_stats()
        if stats and stats[0] is not None:
            break
        time.sleep(0.5)
    replica_stats = fd.replica_stats()
    fd.close()
    chaos_s = time.perf_counter() - t0

    snap = obs.snapshot()
    counters = snap["counters"]
    failures: list[str] = []

    if final is None:
        failures.append(
            f"never converged on epoch {target} within {args.timeout}s "
            f"(issued={issued} acked={acked} detected={detected})"
        )
    else:
        if int(final.get("epoch", -1)) != target:
            failures.append(f"converged on wrong epoch: {final.get('epoch')}")
        if final.get("root") != ctl.hex():
            failures.append(
                "restored root differs from uninterrupted control run: "
                f"{final.get('root')} != {ctl.hex()}"
            )
        lineage = final.get("lineage") or {}
        if lineage.get("verdict") != "restored":
            failures.append(
                f"replacement did not restore-then-replay: lineage={lineage}"
            )
        if (lineage.get("epoch_span") or [None, None])[1] != target:
            failures.append(f"LATEST lineage not at target epoch: {lineage}")
    if detected < 1:
        failures.append("no RPC ever failed: the kill never hit an in-flight request")
    replaced = counters.get("frontdoor.replicas_replaced", 0)
    if replaced < 1:
        failures.append("frontdoor.replicas_replaced == 0 (kill never happened "
                        "or was never healed)")
    rec_hist = snap["histograms"].get("serve.stage_ms.recovery", {})
    if not rec_hist.get("count"):
        failures.append("serve.stage_ms.recovery is empty — the recovery stage "
                        "never reached the merged waterfall")
    if dishonest:
        failures.append(
            f"{len(dishonest)} busy replies without honest retry_after_s: "
            f"{dishonest[:3]}"
        )
    if scrub_rep is None or not scrub_rep.get("ok"):
        failures.append(f"post-recovery scrub failed: {scrub_rep}")
    elif scrub_rep.get("mismatches") or not scrub_rep.get("checks"):
        failures.append(f"post-recovery scrub not clean: {scrub_rep}")
    if not replica_stats or replica_stats[0] is None:
        failures.append("replacement never answered a health probe")
    else:
        cold = replica_stats[0].get("compiles_after_ready")
        if cold:
            failures.append(f"{cold} cold compiles after ready on the replacement")
        resident_health = (replica_stats[0].get("resident") or {}).get("lineage") or {}
        if not resident_health.get("manifest"):
            failures.append(
                f"no checkpoint lineage in health: {replica_stats[0]}"
            )
    bundles = []
    if os.path.isdir(pm_dir):
        bundles = [
            os.path.join(pm_dir, n)
            for n in sorted(os.listdir(pm_dir))
            if n.startswith("postmortem-") and "frontdoor-replica-lost" in n
        ]
    if not bundles:
        failures.append(f"no frontdoor.replica_lost postmortem bundle in {pm_dir}")

    report = {
        "ok": not failures,
        "failures": failures,
        "target_epoch": target,
        "validators": args.validators,
        "root": ctl.hex(),
        "advances": {"issued": issued, "acked": acked, "detected_failures": detected,
                     "busy_replies": busy_seen},
        "replicas_replaced": replaced,
        "recovery_ms": rec_hist,
        "lineage": (final or {}).get("lineage"),
        "scrub": scrub_rep,
        "postmortem_bundles": bundles,
        "control_s": round(control_s, 3),
        "chaos_s": round(chaos_s, 3),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps({k: report[k] for k in
                      ("ok", "advances", "replicas_replaced", "lineage")}))
    if failures:
        flight.trigger_dump(
            "recovery_smoke.gate", detail="; ".join(failures)[:500],
            extra={"failures": failures, "report": report},
        )
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
