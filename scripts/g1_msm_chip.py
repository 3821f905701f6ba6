#!/usr/bin/env python3
"""The G1 multi-MSM program (`ops/g1_msm.msm_many_kernel`) alone, arguments
resident on the device, at the two widths the benchmark's cells run it
(2 items x 32 lanes, a block of six blob sidecars; 256 x 32, a block of
128 data column sidecars x 21 blobs), on the machine this is started on.
One JSON line a shape: the first call (compile or cache load included),
then the least of `--repeat` calls, and whether the points equal the
host's MSMs.

    python scripts/g1_msm_chip.py [--shapes 2x32 256x32] [--repeat 6]

Fails without an accelerator. Scalars are full-width draws from the seed
(the cells' RLC scalars are), over 64 distinct points: the program's time
does not depend on its data.
"""

from __future__ import annotations

import argparse
import json
import os
import random
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
    ap.add_argument("--shapes", nargs="*", default=["2x32", "256x32"])
    ap.add_argument("--repeat", type=int, default=6)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--check-items", type=int, default=4,
                    help="items compared with the host's MSM a shape")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("g1_msm_chip: no accelerator", file=sys.stderr)
        return 2

    from eth_consensus_specs_tpu.crypto.curve import g1_generator
    from eth_consensus_specs_tpu.crypto.fields import R
    from eth_consensus_specs_tpu.crypto.msm import msm_g1
    from eth_consensus_specs_tpu.ops import g1_msm as gm
    from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    rng = random.Random(args.seed)
    pool = [g1_generator().mul(rng.randrange(1, R)) for _ in range(64)]
    pool_limbs = gm._points_to_limbs(pool)
    wrong = 0
    for shape in args.shapes:
        items, lanes = (int(n) for n in shape.split("x"))
        pick = np.asarray([rng.randrange(len(pool)) for _ in range(items * lanes)])
        scalars = [rng.randrange(1 << gm.SCALAR_BITS) for _ in range(items * lanes)]
        bits = gm._scalars_to_bits(scalars).reshape(items, lanes, gm.SCALAR_BITS)
        coords = [c[pick].reshape(items, lanes, gm.N_LIMBS) for c in pool_limbs]
        device_args = [jax.device_put(jnp.asarray(a)) for a in (bits, *coords)]

        out, first, best = timed(
            lambda: jax.block_until_ready(gm.msm_many_kernel(*device_args)), args.repeat)
        check = min(items, args.check_items)
        got = gm._jacobian_to_points(*(np.asarray(a)[:check] for a in out))
        want = [
            msm_g1([pool[j] for j in pick[i * lanes : (i + 1) * lanes]],
                   scalars[i * lanes : (i + 1) * lanes])
            for i in range(check)
        ]
        wrong += got != want
        print(json.dumps({
            "program": "msm_many_kernel", "shape": shape, "first_s": round(first, 3),
            "best_ms": round(best * 1e3, 3), "equal": got == want, "items_checked": check,
            # None from a checkout older than the windowed loop
            "scalar_steps": getattr(gm, "SCALAR_STEPS", None),
            "field_muls": getattr(gm, "SCALAR_FIELD_MULS", None),
            "device": dev.device_kind,
        }), flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
