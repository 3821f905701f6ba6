#!/usr/bin/env python3
"""An epoch's shuffle at a mainnet active set both ways, on the machine this
is started on: the host's numpy form against the device program
(`ops/shuffle.shuffle_rounds_kernel`), the numbers the routing of
`ops/shuffle.shuffled_indices` is set beside (PERF.md section 5), and the
program's parts alone: the whole program with its arguments resident, and
its decision hashes. One JSON line a leg: the first call (compile or cache
load included), then the least of `--repeat` calls.

    python scripts/shuffle_legs_chip.py [--active 1044403] [--repeat 5]

Fails without an accelerator.
"""

from __future__ import annotations

import argparse
import json
import os
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
    ap.add_argument("--active", type=int, default=1044403)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("shuffle_legs_chip: no accelerator", file=sys.stderr)
        return 2

    from eth_consensus_specs_tpu.ops import shuffle
    from eth_consensus_specs_tpu.serve import buckets
    from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    n, rounds = args.active, shuffle.mainnet_rounds()
    lanes = buckets.shuffle_key(n)[1]

    def emit(leg: str, where: str, first: float, best: float, **more) -> None:
        print(json.dumps({"leg": leg, "where": where, "first_ms": round(first * 1e3, 3),
                          "best_ms": round(best * 1e3, 3), "device": dev.device_kind,
                          "active": n, "lanes": lanes, **more}), flush=True)

    rng = np.random.default_rng(args.seed)
    seed = rng.bytes(32)
    active = np.sort(rng.choice(1 << 21, n, replace=False)).astype(np.int32)

    # ---- both routes, as the served verb runs them
    want, first, best = timed(
        lambda: shuffle.shuffled_indices_host(active, seed, rounds), 1)
    emit("request", "host route (numpy, hashlib)", first, best)
    got, first, best = timed(
        lambda: shuffle.shuffled_indices_device(active, seed, rounds, lanes=lanes), args.repeat)
    emit("request", "device route (pack, call, unpack)", first, best,
         equal=bool((got == want).all()))

    # ---- the program alone, its arguments on the device
    padded = np.zeros(lanes, np.int32)
    padded[:n] = active
    seed_words = jax.device_put(np.frombuffer(seed, ">u4").astype(np.uint32))
    pivots = jax.device_put(shuffle._pivots(seed, n, rounds))
    on_device = jax.device_put(padded)
    count = jax.device_put(np.int32(n))
    _, first, best = timed(
        lambda: shuffle.shuffle_rounds_kernel(seed_words, pivots, count, on_device)
        .block_until_ready(), args.repeat)
    emit("program", "shuffle_rounds_kernel, arguments resident", first, best)

    # ---- its decision hashes alone
    hashes = jax.jit(shuffle._decision_digests, static_argnums=(1, 2))
    _, first, best = timed(
        lambda: hashes(seed_words, rounds, lanes // 256).block_until_ready(), args.repeat)
    emit("decision hashes", f"{rounds} x {lanes // 256} single-block messages", first, best)

    print(json.dumps({"seconds": round(time.perf_counter() - t_start, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
