#!/usr/bin/env python3
"""Each leg of a block's attestation flush (128 aggregates x 512 keys) on
the device and through the C core, on the machine this is started on: the
table the served routing of `ops/bls_batch.verify_many` is made from
(PERF.md section 5). One JSON line a leg: the first call (compile or cache
load included), then the least of `--repeat` calls.

    python scripts/bls_legs_chip.py [--items 128] [--lanes 512] [--keys 1048576]
        [--strips 64] [--skip h2c] [--budget-s 1200]

The device hash-to-G2 is skipped unless `--skip ""` is given: compiling it met
the 40 GiB of a one-chip machine's host and the call was killed (PR 27). The
device pairing takes ~12 minutes to compile the first time.

Fails without an accelerator. The keys are multiples of the generator (a
few thousand distinct points tiled to the table's size: the sums do not
care), the signatures and messages random: no verdict is checked here,
only that the device's answers equal the core's.
"""

from __future__ import annotations

import argparse
import json
import os
import secrets
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, repeat: int):
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    best = first
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, first, best


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--items", type=int, default=128)
    ap.add_argument("--lanes", type=int, default=512)
    ap.add_argument("--keys", type=int, default=1 << 20)
    ap.add_argument("--strips", default="64")
    ap.add_argument("--skip", default="h2c")
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--budget-s", type=float, default=1200.0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("bls_legs_chip: no accelerator", file=sys.stderr)
        return 2

    from eth_consensus_specs_tpu.crypto import native_bridge as nb
    from eth_consensus_specs_tpu.crypto.curve import (
        g1_generator, g1_to_bytes, g2_from_bytes, g2_generator, g2_to_bytes,
    )
    from eth_consensus_specs_tpu.crypto.hash_to_curve import hash_to_g2
    from eth_consensus_specs_tpu.crypto.pairing import pairing_check
    from eth_consensus_specs_tpu.ops import g1_msm
    from eth_consensus_specs_tpu.ops.key_table import KeyTable
    from eth_consensus_specs_tpu.utils.bls import multi_exp
    from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    skip = set(filter(None, args.skip.split(",")))

    def emit(leg: str, where: str, first: float, best: float, **more) -> None:
        print(json.dumps({"leg": leg, "where": where, "first_ms": round(first * 1e3, 3),
                          "best_ms": round(best * 1e3, 3), "device": dev.device_kind,
                          "items": args.items, "lanes": args.lanes, **more}), flush=True)

    def in_budget(leg: str) -> bool:
        if time.perf_counter() - t_start < args.budget_s:
            return True
        print(json.dumps({"leg": leg, "skipped": "budget"}), flush=True)
        return False

    # ---- keys: a table of --keys rows over 4,096 distinct points
    g, distinct = g1_generator(), 4096
    p, pubkeys = g.mul(secrets.randbits(200) | 1), []
    for _ in range(distinct):
        pubkeys.append(g1_to_bytes(p))
        p = p + g
    t0 = time.perf_counter()
    table = KeyTable(pubkeys)
    emit("key_validate", "C core, threads", time.perf_counter() - t0,
         (time.perf_counter() - t0), keys=distinct)
    table.affine = np.tile(table.affine, (args.keys // distinct, 1))
    t0 = time.perf_counter()
    tx, ty = table.device_limbs()
    jax.block_until_ready((tx, ty))
    emit("key_limbs_to_device", "host + transfer", time.perf_counter() - t0,
         time.perf_counter() - t0, keys=len(table))
    rng = np.random.default_rng(7)
    rows = [rng.choice(len(table), size=max(args.lanes - int(rng.integers(0, 9)), 1), replace=False)
            .astype(np.int32) for _ in range(args.items)]

    # ---- committee sums
    def core_sums():
        return [nb.g1_aggregate_affine(table.affine[r].tobytes()) for r in rows]

    want, first, best = timed(core_sums, args.repeat)
    emit("g1_sum", "C core, keys resident", first, best)
    index = np.full((args.items, args.lanes), -1, np.int32)
    for i, r in enumerate(rows):
        index[i, : len(r)] = r
    for strip in (int(s) for s in args.strips.split(",")):
        if not in_budget(f"g1_sum strip {strip}"):
            continue

        def device_sums():
            out = g1_msm.sum_indexed_kernel(tx, ty, jax.numpy.asarray(index), strip=strip)
            return [np.asarray(a) for a in out]

        got, first, best = timed(device_sums, args.repeat)
        points = g1_msm._jacobian_to_points(*got)
        equal = all((None if q.is_infinity() else (q.x.n, q.y.n)) == w
                    for q, w in zip(points, want))
        emit("g1_sum", f"device, strip {strip}", first, best, equal=equal)

    def unpack():
        return [p.mul(k) for p, k in zip(g1_msm._jacobian_to_points(*got), scalars)]

    scalars = [secrets.randbits(64) | 1 for _ in range(args.items)]
    terms, first, best = timed(unpack, args.repeat)
    emit("g1_sum.unpack", "host: to affine, 64-bit multiply in the C core", first, best)

    # ---- signatures, messages
    g2 = g2_generator()
    sig_bytes = [g2_to_bytes(g2.mul(secrets.randbits(250) | 1)) for _ in range(args.items)]
    sigs, first, best = timed(lambda: [g2_from_bytes(b) for b in sig_bytes], args.repeat)
    emit("sig_decompress", "C core", first, best)
    _, first, best = timed(lambda: multi_exp(sigs, scalars), args.repeat)
    emit("g2_fold", "C core (Pippenger); the device has no G2 MSM", first, best)
    msgs = [secrets.token_bytes(32) for _ in range(args.items)]
    hashed, first, best = timed(lambda: [hash_to_g2(m) for m in msgs], args.repeat)
    emit("h2c", "C core", first, best)
    if "h2c" not in skip and in_budget("h2c device"):
        from eth_consensus_specs_tpu.ops.h2c_device import hash_to_g2_device

        got_h, first, best = timed(lambda: hash_to_g2_device(msgs), 2)
        emit("h2c", "device", first, best, equal=got_h == hashed)

    # ---- the RLC pairing: items + 1 pairs
    pairs = [(t, h) for t, h in zip(terms, hashed)] + [(-g, multi_exp(sigs, scalars))]
    want_ok, first, best = timed(lambda: pairing_check(pairs), args.repeat)
    emit("pairing", "C core", first, best, pairs=len(pairs))
    if "pairing" not in skip and in_budget("pairing device"):
        from eth_consensus_specs_tpu.ops.pairing_device import pairing_check_device

        got_ok, first, best = timed(lambda: pairing_check_device(pairs), 2)
        emit("pairing", "device", first, best, pairs=len(pairs), equal=got_ok == want_ok)
    print(json.dumps({"seconds": round(time.perf_counter() - t_start, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
