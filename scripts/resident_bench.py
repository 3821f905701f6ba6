"""Resident-loop incremental-merkleization bench — the resident-smoke gate.

Measures `parallel/resident.run_epochs` with the FULL per-epoch state
recompute (``with_root="state"``) against the incremental merkle_inc
forest (``with_root="state_inc"``) on the same synthetic registry, and
gates the contract the incremental path ships under:

  1. **bit parity** — the incremental xor-chain ``root_acc`` equals the
     full recompute's on every timed repeat (same salted columns);
  2. **mesh parity** — with ``--chips N`` the forest's leaf axes shard
     over the (dp, sp) mesh and the sharded ``root_acc`` must equal the
     single-device one bit for bit;
  3. **zero cold compiles after warmup** — every runner/forest shape is
     compiled in the warmup phase (``serve.compiles`` via the resident
     first_dispatch keys); a timed dispatch that compiles fails the run;
  4. **speedup** — incremental beats the full recompute by at least
     ``--speedup-min`` (``ETH_SPECS_INC_SPEEDUP_MIN``; interleaved
     best-of-N so host-load noise hits both paths alike).

The report JSON lands in ``--out`` (plus a validated Prometheus
textfile next to it) and carries a ``resident`` section. CI runs
``--smoke --chips 8`` under forced 8-virtual-device XLA (the
resident-smoke job in checks.yml) and uploads both artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from prejax import force_virtual_chips  # noqa: E402

force_virtual_chips()

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from eth_consensus_specs_tpu import obs  # noqa: E402
from eth_consensus_specs_tpu.obs import export, flight  # noqa: E402


def _root_bytes(acc) -> bytes:
    return np.asarray(acc).astype(">u4", order="C").view(np.uint8).tobytes()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small CI run")
    ap.add_argument("--n", type=int, default=1 << 16, help="validator count")
    ap.add_argument("--epochs", type=int, default=2, help="chained epochs per run")
    ap.add_argument("--reps", type=int, default=3, help="timed repeats (best-of)")
    ap.add_argument("--chips", type=int,
                    default=int(os.environ.get("ETH_SPECS_SERVE_CHIPS", "0") or 0),
                    help="also run the mesh-sharded forest on N chips")
    ap.add_argument("--speedup-min", type=float,
                    default=float(os.environ.get("ETH_SPECS_INC_SPEEDUP_MIN", "2.0")
                                  or 2.0),
                    help="minimum incremental-vs-full speedup factor")
    ap.add_argument("--out", default="BENCH_RESIDENT.json")
    args = ap.parse_args()
    if args.smoke:
        args.n = min(args.n, 1 << 12)
        args.reps = min(args.reps, 3)

    import __graft_entry__ as graft
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops.state_root import synthetic_static
    from eth_consensus_specs_tpu.parallel import resident
    from eth_consensus_specs_tpu.parallel.mesh_ops import mesh_signature, serve_mesh

    export.maybe_serve_http()
    n, epochs = args.n, args.epochs
    spec = get_spec("deneb", "mainnet")
    cols, just = graft._example_altair_inputs(n)
    cols = jax.device_put(cols)
    just = jax.device_put(just)
    static = synthetic_static(spec, n)
    plan1 = resident.forest_plan_for(static)
    mesh = serve_mesh(args.chips) if args.chips > 1 else None
    planN = resident.forest_plan_for(static, mesh=mesh) if mesh is not None else None

    failures: list[str] = []

    def run_full(c):
        return resident.run_epochs(spec, c, just, epochs, with_root="state",
                                   static=static)

    def run_inc(c, m=None):
        forest, _ = resident.build_state_forest_device(static, c, mesh=m)
        jax.block_until_ready(forest)  # ingest is setup, not timed work
        t0 = time.perf_counter()
        carry = resident.run_epochs(spec, c, just, epochs, with_root="state_inc",
                                    static=static, forest=forest, mesh=m)
        jax.block_until_ready(carry.root_acc)
        return carry, time.perf_counter() - t0

    # --- warmup: every executable compiles here, none in the timed phase
    warm_full = run_full(cols)
    jax.block_until_ready(warm_full.root_acc)
    warm_inc, _ = run_inc(cols)
    if _root_bytes(warm_inc.root_acc) != _root_bytes(warm_full.root_acc):
        failures.append("warmup: incremental root_acc != full recompute root_acc")
    mesh_section = {"chips": args.chips, "shards": 0, "signature": ""}
    if mesh is not None:
        warm_mesh, _ = run_inc(cols, mesh)
        mesh_section = {
            "chips": args.chips,
            "shards": planN.shards,
            "signature": mesh_signature(mesh),
            "parity": _root_bytes(warm_mesh.root_acc) == _root_bytes(warm_inc.root_acc),
        }
        if planN.shards <= 1:
            failures.append(
                f"--chips {args.chips} requested but the forest plan fell back "
                f"to 1 shard (devices: {len(jax.local_devices())})"
            )
        if not mesh_section["parity"]:
            failures.append(
                f"mesh parity: {planN.shards}-shard incremental root_acc != "
                "single-device root_acc"
            )
    compiles_after_warmup = obs.snapshot()["counters"].get("serve.compiles", 0)

    # --- timed phase: interleaved best-of-N, fresh salted columns ---------
    salt_fn = jax.jit(lambda c, s: c._replace(balance=c.balance + s))
    best_full = best_inc = best_mesh = float("inf")
    for i in range(args.reps):
        fresh = salt_fn(cols, jnp.uint64(i + 1))
        jax.block_until_ready(fresh)
        t0 = time.perf_counter()
        full = run_full(fresh)
        jax.block_until_ready(full.root_acc)
        best_full = min(best_full, time.perf_counter() - t0)
        inc, t_inc = run_inc(fresh)
        best_inc = min(best_inc, t_inc)
        if _root_bytes(inc.root_acc) != _root_bytes(full.root_acc):
            failures.append(f"rep {i}: incremental root_acc != full root_acc")
        if mesh is not None:
            incN, t_incN = run_inc(fresh, mesh)
            if _root_bytes(incN.root_acc) != _root_bytes(inc.root_acc):
                failures.append(f"rep {i}: mesh root_acc != single-device root_acc")
            # same best-of-N discipline as the single-device timings —
            # a last-rep host-load spike must not be the reported number
            best_mesh = min(best_mesh, t_incN)
            mesh_section["inc_ms_per_epoch"] = round(best_mesh / epochs * 1e3, 2)

    speedup = best_full / best_inc if best_inc else 0.0
    if speedup < args.speedup_min:
        failures.append(
            f"incremental speedup {speedup:.2f}x < gate {args.speedup_min}x "
            f"(full {best_full/epochs*1e3:.1f} ms/epoch vs "
            f"inc {best_inc/epochs*1e3:.1f} ms/epoch)"
        )

    # --- zero cold compiles after warmup ---------------------------------
    snap = obs.snapshot()
    extra = snap["counters"].get("serve.compiles", 0) - compiles_after_warmup
    if extra > 0:
        failures.append(
            f"{extra} compiles AFTER warmup (a resident shape escaped the "
            "warmup phase's first dispatches)"
        )
    obs.count("serve.compiles_after_warmup", max(extra, 0))
    if snap["watchdog"]["divergences"] != 0:
        failures.append(f"watchdog divergences: {snap['watchdog']}")

    snap = obs.snapshot()
    report = {
        "mode": "resident-smoke" if args.smoke else "resident",
        "n": n,
        "epochs": epochs,
        "reps": args.reps,
        "platform": jax.default_backend(),
        "resident": {
            "epoch_plus_root_full_ms": round(best_full / epochs * 1e3, 3),
            "epoch_plus_root_ms": round(best_inc / epochs * 1e3, 3),
            "incremental_root_speedup": round(speedup, 2),
        },
        "plan": plan1._asdict(),
        "mesh": mesh_section,
        "speedup_min": args.speedup_min,
        "compiles": snap["counters"].get("serve.compiles", 0),
        "compiles_after_warmup": max(extra, 0),
        "inc_roots": snap["counters"].get("state_root.inc_roots", 0),
        "watchdog": snap["watchdog"],
        "failures": failures,
    }
    prom_path = os.environ.get("ETH_SPECS_OBS_PROM") or (
        os.path.splitext(args.out)[0] + ".prom"
    )
    export.write_textfile(prom_path, snap=snap)
    try:
        export.validate_text(open(prom_path).read())
    except ValueError as exc:
        failures.append(f"prometheus exposition invalid: {exc}")
    report["prometheus_textfile"] = prom_path
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    print(json.dumps(report, sort_keys=True))
    if failures:
        flight.trigger_dump("resident_bench.failure", detail="; ".join(failures)[:300])
        print("FAILED:", *failures, sep="\n  ", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
