#!/usr/bin/env python3
"""Each leg of a block's data column sidecars (128 sidecars x 21 blobs:
2,688 cells and proofs) on the device and through the host and the C core,
on the machine this is started on: the table the routing of
`ops/das_batch.verify_many_columns` is set beside (PERF.md section 5). One
JSON line a leg: the first call (compile or cache load included), then the
least of `--repeat` calls.

    python scripts/das_legs_chip.py [--columns 128] [--blobs 21] [--repeat 5]

Fails without an accelerator. The block is a real one (valid cells and
proofs by the testing setup's trapdoor, benchmark/reference/das_ref.py), so
that the whole flush's verdicts can be compared both ways at the end.
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
    ap.add_argument("--columns", type=int, default=128)
    ap.add_argument("--blobs", type=int, default=21)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("das_legs_chip: no accelerator", file=sys.stderr)
        return 2

    from benchmark.reference import das_ref
    from eth_consensus_specs_tpu.crypto import kzg
    from eth_consensus_specs_tpu.crypto import native_bridge as nb
    from eth_consensus_specs_tpu.obs import waterfall
    from eth_consensus_specs_tpu.ops import das_batch
    from eth_consensus_specs_tpu.serve import buckets
    from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

    enable_persistent_cache()

    def emit(leg: str, where: str, first: float, best: float, **more) -> None:
        print(json.dumps({"leg": leg, "where": where, "first_ms": round(first * 1e3, 3),
                          "best_ms": round(best * 1e3, 3), "device": dev.device_kind,
                          "columns": args.columns, "blobs": args.blobs, **more}), flush=True)

    # ---- the block
    t0 = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    blobs = [das_ref.extend_and_prove(das_ref.random_blob(rng)) for _ in range(args.blobs)]
    commitments = tuple(c for c, _, _ in blobs)
    items = [
        (col, tuple(cells[col] for _, cells, _ in blobs), commitments,
         tuple(proofs[col] for _, _, proofs in blobs))
        for col in rng.permutation(das_ref.NUMBER_OF_COLUMNS)[: args.columns].tolist()
    ]
    emit("generate", "reference, trapdoor", time.perf_counter() - t0, time.perf_counter() - t0)

    # ---- prep: structure checks, the points
    columns, first, best = timed(lambda: das_batch.prepare_columns(items), args.repeat)
    emit("prepare_columns", "C core, threads", first, best, proofs=args.columns * args.blobs)
    sample = [p for item in items[:2] for p in item[3]]
    with nb.disabled():
        _, first, best = timed(lambda: das_batch._decode_g1(sample), 1)
    emit("decode_g1", "Python, a point", first / len(sample), best / len(sample))

    # ---- the fold
    fold, first, best = timed(lambda: das_batch._fold(columns), args.repeat)
    emit("das.fold", "host", first, best, cells=len(fold.cells))

    # ---- interpolation: a transform a cell and the fold on the host, or the
    # ONE device program from the cells' bytes to a folded row a sidecar
    fft_key, msm_key = das_batch._bucket_keys(columns)
    interp_key = buckets.das_fold_key(fft_key[1], len(columns))
    host_rows, first, best = timed(lambda: das_batch._host_coefficients(fold), 1)
    emit("interpolation", "host, integers from bytes and a transform a cell", first, best)
    want_interp, first, best = timed(
        lambda: das_batch._interp_fold(columns, fold, host_rows), args.repeat)
    emit("das.interp_fold", "host, over the host's rows", first, best)
    legs = ("das.interp_fold", "fr_fft.pack", "fr_fft.call", "fr_fft.unpack")

    def device_interp():
        ledger = waterfall.open_flush()
        try:
            return das_batch._device_interp(columns, fold, interp_key), dict(ledger)
        finally:
            waterfall.close_flush()

    (interp, _), first, best = timed(device_interp, args.repeat)
    emit("interpolation", f"device, {interp_key[1]} rows folded into {interp_key[2]} sidecars",
         first, best, equal=interp == want_interp,
         legs_ms={k: round(v, 3) for k, v in device_interp()[1].items() if k in legs})

    # ---- the proof sums
    want_sums, first, best = timed(
        lambda: das_batch._partial_sums(columns, fold, msm_key, device=False), 1)
    emit("proof sums", "C core, an MSM an item (Pippenger)", first, best,
         items=2 * len(columns))

    def whole_flush():
        points = [p for col in columns for p in col.proof_points]
        h64 = [das_batch._coset_tables()[col.index][0] for col in columns for _ in col.proofs]
        shifted = [r * h % kzg.BLS_MODULUS for r, h in zip(fold.r_powers, h64)]
        return das_batch._host_msm(points, fold.r_powers), das_batch._host_msm(points, shifted)

    whole, first, best = timed(whole_flush, 1)
    emit("proof sums", "C core, two MSMs over the whole flush (no per-sidecar sums)", first, best)
    got_sums, first, best = timed(
        lambda: das_batch._partial_sums(columns, fold, msm_key, device=True), args.repeat)
    emit("proof sums", f"device, {msm_key[1]} x {msm_key[2]}", first, best,
         equal=got_sums == want_sums
         and whole == (das_batch._sum_points(got_sums[0]), das_batch._sum_points(got_sums[1])))

    # ---- the check
    flush = das_batch._Flush(fold.commitments, fold.weights, interp, *got_sums)
    ok, first, best = timed(lambda: das_batch._check(flush, 0, len(columns)), args.repeat)
    emit("das.check", "host: sums, RLC, RLI in the C core, one pairing", first, best, ok=ok)

    # ---- the whole flush, both routes
    buckets.reset_for_tests()
    host, first, best = timed(lambda: das_batch.verify_many_columns(items, parsed=columns), 1)
    emit("flush", "host route", first, best)
    for key in (interp_key, msm_key):
        buckets.note_dispatch(*key)  # both programs ran above: compiled
    got, first, best = timed(lambda: das_batch.verify_many_columns(items, parsed=columns),
                             args.repeat)
    emit("flush", "device route", first, best, equal=got == host, all_valid=all(got))
    print(json.dumps({"seconds": round(time.perf_counter() - t_start, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
