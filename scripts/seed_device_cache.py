"""Warm the device crypto kernels into the persistent compile cache.

Run this ONCE on a machine with a live accelerator (it is a no-op on
XLA:CPU — the cache helper refuses cpu backends).  After it completes,
the compiled pairing and hash-to-G2 chains sit in `.jax_cache` with warm
sentinels next to them, and `bench.py`'s hybrid BLS section will use the
device stages instead of falling back to host-native.

    python scripts/seed_device_cache.py           # both stages
    python scripts/seed_device_cache.py pairing   # just the Miller chain
    python scripts/seed_device_cache.py h2c       # just hash-to-G2

The first compile of each chain is expensive (minutes — it is exactly
the cost this script exists to pay once); subsequent processes load from
the cache in seconds.

NOTE: run under `timeout(1)` where a wedged backend init must not strand
the caller (the bench itself never calls this; its subprocess budgets
bound it).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    stages = sys.argv[1:] or ["pairing", "h2c"]
    unknown = set(stages) - {"pairing", "h2c"}
    if unknown:
        print(f"unknown stage(s): {sorted(unknown)} — valid: pairing, h2c")
        return 2
    from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

    cache = enable_persistent_cache()
    if cache is None:
        print("no accelerator backend (or init failed) — nothing to seed")
        return 1
    print(f"persistent cache: {cache}")

    if "pairing" in stages:
        from eth_consensus_specs_tpu.crypto.curve import g1_generator, g2_generator
        from eth_consensus_specs_tpu.ops.pairing_device import pairing_check_device

        g1, g2 = g1_generator(), g2_generator()
        pairs = [(g1.mul(6), g2), (g1.mul(2).mul(3), -g2)]
        t0 = time.perf_counter()
        ok = pairing_check_device(pairs)
        print(f"pairing chain: ok={ok} in {time.perf_counter() - t0:.1f}s")
        if not ok:
            return 1

    if "h2c" in stages:
        from eth_consensus_specs_tpu.crypto.hash_to_curve import hash_to_g2
        from eth_consensus_specs_tpu.ops.h2c_device import hash_to_g2_device

        msgs = [b"seed-0", b"seed-1"]
        t0 = time.perf_counter()
        got = hash_to_g2_device(msgs)
        assert all(g == hash_to_g2(m) for g, m in zip(got, msgs))
        print(f"h2c chain: bit-exact in {time.perf_counter() - t0:.1f}s")

    return 0


if __name__ == "__main__":
    raise SystemExit(main())
