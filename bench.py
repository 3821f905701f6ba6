"""Driver benchmark — ONE JSON line on stdout.

Primary metric: SSZ merkleization throughput (device tree kernel,
ops/merkle.py) over a 2**21-chunk leaf level — the size class of a
~1M-validator registry's balance/leaf levels, the reference's #1 hot spot
(hash_tree_root(state) twice per slot; reference:
specs/phase0/beacon-chain.md:1383-1393 via utils/hash_function.py).

Baseline: the reference's exact host path — one hashlib.sha256 call per
tree node (reference: utils/merkle_minimal.py:47-91 hashes pairwise per
level) — measured on a 2**16 subtree and scaled per-hash (hashlib cost is
size-independent per 64B message).

vs_baseline is the speedup of the device tree over that host loop (>1 is
faster than the reference path).

Methodology (round-5: correctness-coupled, roofline-gated):

* CHAINED-DEPENDENCY timing — K iterations inside one jit where
  iteration i+1 consumes iteration i's output, so a lone dispatch's
  apparently-instant completion cannot leak in (round-2: ~7x inflation).
* CORRECTNESS COUPLING — every device section's final chained output is
  recomputed on the host from the SAME salted inputs (native-SHA oracle
  for tree/resident roots, ops/state_root_host.py; an XLA:CPU re-run for
  the epoch/das carries) and the number is REFUSED when the device result
  does not match bit-for-bit.  If the device didn't do the work, the
  metric dies (round-4 verdict weak #1: 878 Ghash/s published from a
  platform that plausibly returned before executing).
* ROOFLINE GATE — each accelerator section reports the implied HBM
  traffic of its measured rate; entries exceeding a configured
  single-chip bound (2x v5e-class 819 GB/s) are refused from both the
  headline and BENCH_LKG.json.  Real hash work is reported alongside
  logical nodes (the fixed-width level loop executes
  ops/merkle.tree_real_hashes(depth) compressions, depth/2 times the
  exact 2**depth - 1; see ops/merkle.tree_root_words).
* BLS timing uses FRESH messages every timed repeat — all hash-to-G2 and
  G2-prepare work happens inside the timed region (round-4 ADVICE: the
  old loop re-verified cached messages, measuring a cache, not the
  pipeline); pubkeys stay fixed across repeats (registry keys repeat
  every block — decompression caching is genuine steady-state).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import numpy as np

# Gate logic (roofline verdicts, result digests) is framework
# infrastructure now — obs/gates.py is the single implementation this
# driver, the obs span registry, the watchdog, and the tests all share.
from eth_consensus_specs_tpu.obs import gates


_LKG_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_LKG.json")

_ACC_TIMEOUT_S = int(os.environ.get("ETH_SPECS_BENCH_ACC_TIMEOUT", "480"))
_CPU_TIMEOUT_S = int(os.environ.get("ETH_SPECS_BENCH_CPU_TIMEOUT", "300"))
_VERIFY_TIMEOUT_S = int(os.environ.get("ETH_SPECS_BENCH_VERIFY_TIMEOUT", "420"))
_MAX_ACC_FAILURES = 3


def _section_timeout(section: str, base_s: int) -> int:
    """Per-section budget scaling: the resident section compiles and
    times TWO full-state chains (full recompute + incremental forest,
    plus per-repeat forest builds), so it gets twice the standard
    budget on EVERY lane — the accelerator run is exactly the one that
    must re-earn the quarantined LKG entry and must not be killed by a
    budget sized for the old single-chain section."""
    return base_s * (2 if section == "resident" else 1)


def _cpu_timeout(section: str) -> int:
    return _section_timeout(section, _CPU_TIMEOUT_S)


_digest = gates.digest


def sizes_for(section: str, on_cpu: bool) -> dict:
    """Work sizes per backend class. CPU-fallback sizes are chosen so every
    section finishes well inside its budget with a quotable number
    (round-4 verdict weak #4: fallback produced timeouts and 0.000-lines)."""
    if section == "tree":
        return (
            {"depth": 14, "chain": 4, "repeats": 2}
            if on_cpu
            else {"depth": 21, "chain": 8, "repeats": 2}
        )
    if section == "epoch":
        return (
            {"n": 1 << 16, "chain": 4, "repeats": 2}
            if on_cpu
            else {"n": 1_000_000, "chain": 8, "repeats": 3}
        )
    if section == "resident":
        return (
            {"n": 1 << 16, "epochs": 2, "repeats": 2}
            if on_cpu
            else {"n": 1 << 20, "epochs": 8, "repeats": 2}
        )
    if section == "das":
        return (
            {"batch": 2, "n": 1024, "chain": 8, "repeats": 2}
            if on_cpu
            else {"batch": 16, "n": 8192, "chain": 8, "repeats": 2}
        )
    if section == "block_epoch":
        return (
            {"n": 1 << 14, "atts": 8, "repeats": 2}
            if on_cpu
            else {"n": 1 << 20, "atts": 128, "repeats": 2}
        )
    if section == "bls":
        return {}  # sizes itself: native-core presence picks the batch
    raise SystemExit(f"unknown section {section}")


def host_hashes_per_sec(n_pairs: int = 1 << 16) -> float:
    """The reference's host path: one hashlib.sha256 per tree node."""
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, 256, size=(n_pairs, 64), dtype=np.uint8)
    blobs = [p.tobytes() for p in pairs]
    sha = hashlib.sha256
    t0 = time.perf_counter()
    for b in blobs:
        sha(b).digest()
    dt = time.perf_counter() - t0
    return n_pairs / dt


def native_hashes_per_sec(n_pairs: int = 1 << 19) -> float | None:
    """This framework's host path: one C call per level, SHA-NI inside."""
    from eth_consensus_specs_tpu import native

    if not native.available():
        return None
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=n_pairs * 64, dtype=np.uint8).tobytes()
    t0 = time.perf_counter()
    native.sha256_pairs(data)
    dt = time.perf_counter() - t0
    return n_pairs / dt


# --------------------------------------------------------------- sections --


def run_tree(p: dict) -> dict:
    """Chained device trees; the final chained root is recomputed through
    the native-SHA host oracle from the same salted leaves — a hash
    engine that shares nothing with XLA — and must match bit-for-bit."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from eth_consensus_specs_tpu.ops.merkle import _tree_root_fused
    from eth_consensus_specs_tpu.ops.state_root_host import tree_root_chain_np

    depth, chain, repeats = p["depth"], p["chain"], p["repeats"]
    rng = np.random.default_rng(1)
    base_np = rng.integers(0, 2**32, size=(1 << depth, 8), dtype=np.uint64).astype(
        np.uint32
    )
    base = jax.device_put(jnp.asarray(base_np))

    @jax.jit
    def run(lv, acc0):
        def body(_, carry):
            lv, acc = carry
            return lv, _tree_root_fused(lv ^ acc, depth)

        return lax.fori_loop(0, chain, body, (lv, acc0))[1]

    run_salt = p.get("salt", 0)
    jax.block_until_ready(run(base, jnp.zeros(8, jnp.uint32)))  # compile + warm
    best = float("inf")
    final = None
    for i in range(repeats):
        salt = jnp.full(8, np.uint32(run_salt + i + 1), jnp.uint32)
        t0 = time.perf_counter()
        final = jax.block_until_ready(run(base, salt))
        best = min(best, time.perf_counter() - t0)
    per_tree = best / chain

    expected = tree_root_chain_np(
        base_np, depth, chain, np.full(8, run_salt + repeats, np.uint32)
    )
    verified = bool(np.array_equal(np.asarray(final), expected))

    from eth_consensus_specs_tpu.ops.merkle import tree_real_hashes

    logical = (1 << depth) - 1
    real = tree_real_hashes(depth)  # fixed-width level loop: ops/merkle.py
    return {
        "hps": logical / per_tree,
        "real_hps": real / per_tree,
        "tree_s": per_tree,
        "depth": depth,
        "chain": chain,
        "work_bytes": real * 96,  # 64B read + 32B write per compression
        "verified": verified,
        "verify_how": "native-sha host oracle, same salted leaves",
    }


def _epoch_setup(p: dict):
    """ONE builder for both the timed run and the verify recompute — the
    two sides can never drift apart."""
    import jax
    from jax import lax

    import __graft_entry__ as graft
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops.state_columns import EpochParams, epoch_accounting

    n, chain = p["n"], p["chain"]
    params = EpochParams.from_spec(get_spec("phase0", "mainnet"))
    cols, just = graft._example_inputs(n)
    work_bytes = 2 * sum(a.nbytes for a in jax.tree_util.tree_leaves(cols))

    @jax.jit
    def run(cols, just):
        def body(_, c):
            res = epoch_accounting(params, c, just)
            return c._replace(
                balance=res.balance, effective_balance=res.effective_balance
            )

        return lax.fori_loop(0, chain, body, cols).balance

    return run, cols, just, work_bytes


def run_epoch(p: dict) -> dict:
    """Fused accounting epochs, chained; returns the final balance digest
    for the parent's XLA:CPU recompute to match."""
    import jax
    import jax.numpy as jnp

    run, cols, just, work_bytes = _epoch_setup(p)
    chain, repeats, salt = p["chain"], p["repeats"], p.get("salt", 0)
    cols = jax.device_put(cols)
    just = jax.device_put(just)
    salt_fn = jax.jit(lambda c, s: c._replace(balance=c.balance + s))
    jax.block_until_ready(run(cols, just))
    best = float("inf")
    final = None
    for i in range(repeats):
        fresh = salt_fn(cols, jnp.uint64(salt + i + 1))
        jax.block_until_ready(fresh)
        t0 = time.perf_counter()
        final = jax.block_until_ready(run(fresh, just))
        best = min(best, time.perf_counter() - t0)
    return {
        "epoch_s": best / chain,
        "n": p["n"],
        "chain": chain,
        "work_bytes": work_bytes,
        "digest": _digest(np.asarray(final)),
        "verify_how": "XLA:CPU re-run, same salted columns",
    }


def _resident_work_bytes(cols, hashes: int) -> int:
    """Lower-bound device traffic per resident epoch: column reads/writes
    plus 96 B per REAL hash of the state root. The hash count comes from
    ops/state_root (state_root_real_hashes for the full recompute,
    state_root_inc_real_hashes' dirty-path capacity model for the
    incremental forest) — the same accounting the resident.run_epochs
    span's roofline verdict uses, so bench and the obs registry can
    never disagree on a timing."""
    import jax

    col_bytes = 2 * sum(a.nbytes for a in jax.tree_util.tree_leaves(cols))
    return col_bytes + 96 * hashes


def run_resident(p: dict) -> dict:
    """Device-resident epochs + FULL per-epoch state root (the north-star
    shape), measured BOTH ways on the same salted columns: the full
    re-merkleization and the incremental merkle_inc forest
    (dirty-subtree path updates). The two xor-chain root_accs must be
    bit-identical or the child refuses the number; the headline
    per_epoch_s is the incremental path, the full path rides along for
    the `incremental_root_speedup` factor. Verified at FULL SIZE: the
    parent recomputes root_acc with accounting on XLA:CPU and every
    state root through the native-SHA host oracle
    (ops/state_root_host.resident_root_acc_host)."""
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as graft
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops.state_root import (
        state_root_inc_real_hashes,
        state_root_real_hashes,
        synthetic_static,
    )
    from eth_consensus_specs_tpu.parallel import resident

    n, epochs, repeats = p["n"], p["epochs"], p["repeats"]
    spec = get_spec("deneb", "mainnet")
    cols, just = graft._example_altair_inputs(n)
    cols = jax.device_put(cols)
    just = jax.device_put(just)
    static = synthetic_static(spec, n)
    plan = resident.forest_plan_for(static)
    work_bytes_full = _resident_work_bytes(cols, state_root_real_hashes(static[1]))
    work_bytes_inc = _resident_work_bytes(
        cols, state_root_inc_real_hashes(static[1], plan)
    )

    run_salt = p.get("salt", 0)
    salt_fn = jax.jit(lambda c, s: c._replace(balance=c.balance + s))
    # warm both compiled chains (and the forest builder) off the clock
    jax.block_until_ready(
        resident.run_epochs(spec, cols, just, epochs, with_root="state", static=static).root_acc
    )
    warm_forest, _ = resident.build_state_forest_device(static, cols)
    jax.block_until_ready(warm_forest)
    jax.block_until_ready(
        resident.run_epochs(
            spec, cols, just, epochs, with_root="state_inc", static=static,
            forest=warm_forest,
        ).root_acc
    )
    best_full = best_inc = float("inf")
    final = None
    for i in range(repeats):
        fresh = salt_fn(cols, jnp.uint64(run_salt + i + 1))
        jax.block_until_ready(fresh)
        t0 = time.perf_counter()
        full_acc = jax.block_until_ready(
            resident.run_epochs(
                spec, fresh, just, epochs, with_root="state", static=static
            ).root_acc
        )
        best_full = min(best_full, time.perf_counter() - t0)
        # the forest ingest is one-time setup, rebuilt per repeat because
        # each repeat's salted columns are a different pre-epoch state —
        # built (and COMPLETED: the build is async) outside the timer
        forest, _ = resident.build_state_forest_device(static, fresh)
        jax.block_until_ready(forest)
        t0 = time.perf_counter()
        inc_acc = jax.block_until_ready(
            resident.run_epochs(
                spec, fresh, just, epochs, with_root="state_inc", static=static,
                forest=forest,
            ).root_acc
        )
        best_inc = min(best_inc, time.perf_counter() - t0)
        if bytes(np.asarray(inc_acc)) != bytes(np.asarray(full_acc)):
            raise RuntimeError(
                "incremental root_acc != full-recompute root_acc on the same "
                "salted columns — the incremental path did not compute the "
                "same tree; refusing to publish either number"
            )
        final = inc_acc
    return {
        "per_epoch_s": best_inc / epochs,
        "per_epoch_full_s": best_full / epochs,
        "incremental_root_speedup": round(best_full / best_inc, 2),
        "total_s": best_inc,
        "n": n,
        "epochs": epochs,
        "work_bytes": work_bytes_inc,
        "work_bytes_full": work_bytes_full,
        "dirty_caps": [plan.cap_val, plan.cap_bal],
        "identical": True,
        "digest": _digest(np.asarray(final)),
        "verify_how": "XLA:CPU accounting + native-SHA state roots, same salted "
        "columns; incremental forest root_acc REQUIRED bit-identical to the "
        "full recompute in-child",
    }


def _das_setup(p: dict):
    """Shared builder: the chained-FFT jit plus a per-repeat input maker
    seeded from the run salt (fresh field elements every repeat and every
    bench invocation — nothing a result cache could replay)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from eth_consensus_specs_tpu.crypto.kzg import compute_roots_of_unity
    from eth_consensus_specs_tpu.ops import fr_fft
    from eth_consensus_specs_tpu.ops.fr_fft import FR

    batch, n, chain = p["batch"], p["n"], p["chain"]
    roots = tuple(compute_roots_of_unity(n))
    rev = jnp.asarray(fr_fft._bit_reversal_indices(n))
    twiddles = [jnp.asarray(t) for t in fr_fft._stage_twiddles(roots, n)]

    def make_vals(rep: int) -> np.ndarray:
        rng = np.random.default_rng((7, p.get("salt", 0), rep))
        return FR.ints_to_mont_batch(
            rng.integers(1, 1 << 62, size=(batch, n), dtype=np.int64)
        )

    @jax.jit
    def run(v):
        def body(_, v):
            return fr_fft.fft_stages(jnp.take(v, rev, axis=1), twiddles, n)

        return lax.fori_loop(0, chain, body, v)

    return run, make_vals


def run_das(p: dict) -> dict:
    """Batched Fr FFT rounds, chained; final coefficient digest checked
    against an XLA:CPU re-run by the parent."""
    import jax
    import jax.numpy as jnp

    run, make_vals = _das_setup(p)
    batch, n, chain, repeats = p["batch"], p["n"], p["chain"], p["repeats"]
    dev = jax.device_put(jnp.asarray(make_vals(0)))
    work_bytes = 2 * int(np.asarray(dev).nbytes) * max(n.bit_length() - 1, 1)
    jax.block_until_ready(run(dev))  # compile + warm
    best = float("inf")
    final = None
    for rep in range(1, repeats + 1):
        fresh = jax.device_put(jnp.asarray(make_vals(rep)))
        t0 = time.perf_counter()
        final = jax.block_until_ready(run(fresh))
        best = min(best, time.perf_counter() - t0)
    per_round = best / chain
    return {
        "ffts_per_sec": batch / per_round,
        "round_s": per_round,
        "batch": batch,
        "n": n,
        "work_bytes": work_bytes,
        "digest": _digest(np.asarray(final)),
        "verify_how": "XLA:CPU re-run, same salted inputs",
    }


def _block_epoch_setup(p: dict):
    import __graft_entry__ as graft
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops import block_epoch as bek
    from eth_consensus_specs_tpu.ops.state_root import synthetic_static

    n, atts = p["n"], p["atts"]
    spec = get_spec("deneb", "mainnet")
    cols, st0, static = bek.synthetic_block_columns(spec, n, seed=11, atts_per_slot=atts)
    acols, just = graft._example_altair_inputs(n)
    scores = acols.inactivity_scores
    arrays, meta = synthetic_static(spec, n)
    return spec, bek, cols, st0, static, scores, just, arrays, meta


def run_block_epoch(p: dict) -> dict:
    """An epoch of BLOCKS on device (BASELINE config #4): 32 slots x
    `atts` attestations of committee bit-accumulation, proposer rewards,
    sync rewards, deposits, the capella withdrawal sweep — with a dirty
    state root every slot, all inside one jit (lax.scan over slots).
    Verified against the pure-numpy + native-SHA oracle at FULL size."""
    import jax
    import jax.numpy as jnp

    spec, bek, cols, st0, static, scores, just, arrays, meta = _block_epoch_setup(p)
    n, repeats = p["n"], p["repeats"]
    params = bek.BlockEpochParams.from_spec(spec)
    ctx = bek.make_root_ctx(spec, arrays, meta, static, scores, just)

    @jax.jit
    def run(st):
        out, acc = bek.block_epoch_chain(params, n, st, cols, static, root_ctx=ctx)
        return out.balance, acc

    run_salt = p.get("salt", 0)
    st0 = jax.device_put(st0)
    jax.block_until_ready(run(st0))  # compile + warm
    best = float("inf")
    final = None
    for i in range(repeats):
        fresh = st0._replace(balance=st0.balance + jnp.uint64(run_salt + i + 1))
        jax.block_until_ready(fresh)
        t0 = time.perf_counter()
        final = run(fresh)
        jax.block_until_ready(final)
        best = min(best, time.perf_counter() - t0)
    bal, acc = (np.asarray(final[0]), np.asarray(final[1]))

    slots = params.slots_per_epoch

    # per-slot root accounting shared with the block_epoch.chain span
    # (ops/state_root.slot_root_real_hashes): one implementation, one verdict
    from eth_consensus_specs_tpu.ops.state_root import slot_root_real_hashes

    col_bytes = 2 * sum(
        a.nbytes for a in jax.tree_util.tree_leaves((st0.balance, st0.cur_part, st0.prev_part))
    )
    work_bytes = slots * (96 * slot_root_real_hashes(n, meta.top_depth) + col_bytes)
    return {
        "epoch_s": best,
        "slot_ms": best / slots * 1e3,
        "n": n,
        "atts": p["atts"],
        "slots": slots,
        "work_bytes": work_bytes,
        "digest": hashlib.sha256(acc.tobytes() + bal.tobytes()).hexdigest()[:32],
        "verify_how": "numpy replay + native-SHA slot roots, same salted inputs",
    }


def verify_block_epoch_digest(p: dict) -> str:
    from eth_consensus_specs_tpu.ops import block_epoch_host as bekh

    spec, bek, cols, st0, static, scores, just, arrays, meta = _block_epoch_setup(p)
    n = p["n"]
    params = bek.BlockEpochParams.from_spec(spec)
    fresh = st0._replace(
        balance=np.asarray(st0.balance) + np.uint64(p.get("salt", 0) + p["repeats"])
    )
    root_fn = bekh.slot_root_fn_np(spec, arrays, meta, static, scores, just)
    bal, _cur, _prev, _wi, _wv, acc = bekh.replay_block_epoch_np(
        params,
        n,
        fresh,
        cols,
        np.asarray(static.eff_balance),
        np.asarray(static.withdrawable_epoch),
        np.asarray(static.has_eth1_cred),
        int(np.asarray(static.epoch)),
        root_fn=root_fn,
    )
    return hashlib.sha256(acc.tobytes() + bal.tobytes()).hexdigest()[:32]


def verify_digest(section: str, p: dict) -> str:
    """Recompute the section's expected final digest on THIS backend
    (the parent runs this in a CPU-pinned child). Inputs are rebuilt from
    the same fixed seeds; the salt is the final repeat's."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if section == "block_epoch":
        return verify_block_epoch_digest(p)

    if section == "epoch":
        run, cols, just, _wb = _epoch_setup(p)
        fresh = cols._replace(
            balance=cols.balance + np.uint64(p.get("salt", 0) + p["repeats"])
        )
        return _digest(np.asarray(run(fresh, just)))

    if section == "resident":
        import __graft_entry__ as graft
        from eth_consensus_specs_tpu.forks import get_spec
        from eth_consensus_specs_tpu.ops.state_root import synthetic_static
        from eth_consensus_specs_tpu.ops.state_root_host import resident_root_acc_host

        n, epochs, repeats = p["n"], p["epochs"], p["repeats"]
        spec = get_spec("deneb", "mainnet")
        cols, just = graft._example_altair_inputs(n)
        static = synthetic_static(spec, n)
        fresh = cols._replace(
            balance=cols.balance + np.uint64(p.get("salt", 0) + repeats)
        )
        return _digest(resident_root_acc_host(spec, fresh, just, epochs, static))

    if section == "das":
        run, make_vals = _das_setup(p)
        return _digest(np.asarray(run(jnp.asarray(make_vals(p["repeats"])))))

    raise SystemExit(f"no verify mode for section {section}")


def bench_batch_verify(n_aggregates: int, committee: int = 8, reps: int = 3):
    """Aggregate-signature batch verification with FRESH messages every
    timed repeat: hash-to-G2 and G2-prepare run inside the timed region
    (nothing is served from a message cache).  Pubkeys are fixed across
    repeats — registry keys repeat every block, so decompression caching
    is genuine steady-state, and signing happens outside the timed
    region.  Every timed call must ACCEPT its (valid) batch; a tampered
    batch must REJECT through the same configured path afterwards, or
    the child exits nonzero and no number is published."""
    from eth_consensus_specs_tpu.crypto import signature as sig_mod
    from eth_consensus_specs_tpu.ops.bls_batch import batch_verify_aggregates

    def msg_for(rep: int, i: int) -> bytes:
        return hashlib.sha256(b"bench-bls-%d-%d" % (rep, i)).digest()

    groups, pks = [], []
    sk = 1
    for _ in range(n_aggregates):
        g = list(range(sk, sk + committee))
        sk += committee
        groups.append(g)
        pks.append([sig_mod.sk_to_pk(k) for k in g])

    def items_for(rep: int):
        out = []
        for i, g in enumerate(groups):
            m = msg_for(rep, i)
            out.append((pks[i], m, sig_mod.aggregate([sig_mod.sign(k, m) for k in g])))
        return out

    if not batch_verify_aggregates(items_for(-1)):  # warm: compiles + pk cache
        raise RuntimeError("batch verification rejected valid signatures (warm)")
    # belt + braces on top of the fresh messages: drop the warm call's
    # hash-to-G2 and G2-prepare entries so NOTHING timed below can be
    # served from a message-derived cache (ADVICE round-4 medium)
    from eth_consensus_specs_tpu.ops import bls_batch as _bls_mod
    from eth_consensus_specs_tpu.ops import pairing_device as _pd_mod

    _bls_mod._H2G2_CACHE.clear()
    _pd_mod._PREP_CACHE.clear()
    best = float("inf")
    last = None
    for r in range(reps):
        last = items_for(r)  # fresh messages — built OUTSIDE the timed region
        t0 = time.perf_counter()
        ok = batch_verify_aggregates(last)
        best = min(best, time.perf_counter() - t0)
        if not ok:
            raise RuntimeError("batch verification rejected valid signatures")
    # supplementary CACHE-WARM number, reported separately and clearly
    # labeled: the same (already-verified) batch again, h2c/prepare served
    # from the caches — the steady-state ceiling, never the headline.
    # Only meaningful when a message-derived cache is actually in play
    # (device h2c / prepared pairing): the plain host path recomputes
    # hash_to_g2 per call, and publishing a "warm" rate that is really a
    # 4th cold rep would just be noise — report null instead.
    warm_rate = None
    if _bls_mod._H2G2_CACHE or _pd_mod._PREP_CACHE:
        warm_best = float("inf")
        for _ in range(2):  # min-of-2: same best-of-N discipline as cold
            t0 = time.perf_counter()
            if not batch_verify_aggregates(last):
                raise RuntimeError("batch verification rejected valid signatures (warm rep)")
            warm_best = min(warm_best, time.perf_counter() - t0)
        warm_rate = n_aggregates / warm_best
    bad = list(last)
    bad[0] = (bad[0][0], hashlib.sha256(b"tampered").digest(), bad[0][2])
    if batch_verify_aggregates(bad):
        raise RuntimeError("batch verification ACCEPTED a tampered batch")
    return n_aggregates / best, best, last, warm_rate


def _run_bls(on_cpu: bool, no_cache: bool) -> dict:
    import jax

    from eth_consensus_specs_tpu.native import get_bls_lib

    device_pairing = False
    device_h2c = False
    if not on_cpu and not no_cache:
        # hybrid mode: host C does aggregation/prepare; the RLC
        # Miller/final-exp batch — and optionally batched hash-to-G2 —
        # run on the accelerator.  Stages opt in only when a completed
        # prior run left its compiled chain in the persistent cache
        # (warm sentinels): a cold compile can exceed the whole budget.
        from eth_consensus_specs_tpu.utils.cache import warm_sentinel

        backend = jax.default_backend()
        if os.path.exists(warm_sentinel("pairing", backend)):
            os.environ["ETH_SPECS_TPU_DEVICE_PAIRING"] = "1"
            device_pairing = True
        if os.path.exists(warm_sentinel("h2c", backend)):
            os.environ["ETH_SPECS_TPU_DEVICE_H2C"] = "1"
            device_h2c = True
    n = 64 if get_bls_lib() is not None else 4
    aggs_per_sec, batch_s, last_items, warm_aggs_per_sec = bench_batch_verify(n_aggregates=n)
    cross_checked = None
    if device_pairing or device_h2c:
        # the device-stage verdicts must agree with the host path on the
        # SAME inputs: re-verify the last timed batch with device stages
        # forced OFF — both paths must accept.  The h2c cache still holds
        # DEVICE-computed points for these messages; clear it so the host
        # leg genuinely recomputes hash-to-G2 instead of echoing them.
        from eth_consensus_specs_tpu.ops import bls_batch
        from eth_consensus_specs_tpu.ops.bls_batch import batch_verify_aggregates

        bls_batch._H2G2_CACHE.clear()
        os.environ["ETH_SPECS_TPU_NO_DEVICE_PAIRING"] = "1"
        h2c_was = os.environ.pop("ETH_SPECS_TPU_DEVICE_H2C", None)
        try:
            cross_checked = bool(batch_verify_aggregates(last_items))
        finally:
            del os.environ["ETH_SPECS_TPU_NO_DEVICE_PAIRING"]
            if h2c_was is not None:
                os.environ["ETH_SPECS_TPU_DEVICE_H2C"] = h2c_was
        if not cross_checked:
            raise RuntimeError("device and host BLS paths disagree on the same batch")
    return {
        "aggs_per_sec": aggs_per_sec,
        "batch_s": batch_s,
        # supplementary, repeated msgs; null when no message-derived cache
        # was in play (host h2c recomputes per call — nothing to warm)
        "aggs_per_sec_cache_warm": warm_aggs_per_sec,
        "n": n,
        "fresh_messages": True,
        "pairing": "device-miller" if device_pairing else "host-native-multi-miller",
        "h2c": "device" if device_h2c else "host-native",
        "cross_checked": cross_checked,
        "verified": True,  # in-band: every timed batch accepted, tamper rejected
    }


# ------------------------------------------------------------ child modes --


def _child_main(argv: list[str]) -> None:
    """Child mode: run one section (or a --verify recompute), print a JSON
    fragment carrying the backend it ACTUALLY ran on."""
    section = argv[argv.index("--section") + 1]
    on_cpu = "--cpu" in argv
    no_cache = "--nocache" in argv
    verify = "--verify" in argv
    params = None
    if "--params" in argv:
        params = json.loads(argv[argv.index("--params") + 1])
    salt = int(argv[argv.index("--salt") + 1]) if "--salt" in argv else 0

    if on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # device-pairing one-time compiles dwarf the CPU budget
        os.environ["ETH_SPECS_TPU_NO_DEVICE_PAIRING"] = "1"
    import jax

    if not on_cpu and not no_cache:
        # a corrupt/stale .jax_cache entry must not hang every
        # accelerator attempt (round-3 failure mode)
        from eth_consensus_specs_tpu.utils.cache import enable_persistent_cache

        enable_persistent_cache()

    if verify:
        print(json.dumps({"digest": verify_digest(section, params)}))
        return

    if not on_cpu and jax.default_backend() == "cpu":
        # an accelerator run was requested but the backend fell back to
        # CPU — abort BEFORE burning the section budget on full-size
        # shapes XLA:CPU cannot finish; the parent counts this failure
        # and reruns with CPU-scaled sizes
        print(json.dumps({"backend": "cpu", "aborted": True}))
        return

    if params is None:
        params = sizes_for(section, on_cpu)
        # run-unique salt: every bench invocation submits DIFFERENT salted
        # inputs, so a platform-side (program, input) result cache can
        # never replay a previous run's output through the verifier
        params["salt"] = salt
    if section == "tree":
        payload = run_tree(params)
    elif section == "epoch":
        payload = run_epoch(params)
    elif section == "resident":
        payload = run_resident(params)
    elif section == "das":
        payload = run_das(params)
    elif section == "block_epoch":
        payload = run_block_epoch(params)
    elif section == "bls":
        payload = _run_bls(on_cpu, no_cache)
    else:
        raise SystemExit(f"unknown section {section}")
    payload["backend"] = jax.default_backend()
    payload["params"] = params
    print(json.dumps(payload))


_RUN_SALT = int.from_bytes(os.urandom(3), "big")


def _section_in_subprocess(
    section: str,
    on_cpu: bool,
    timeout_s: int,
    no_cache: bool = False,
    verify: bool = False,
    params: dict | None = None,
) -> dict | None:
    """Run a bench child with a hard timeout — a hung device backend must
    never prevent the final JSON line."""
    import subprocess

    cmd = [sys.executable, __file__, "--section", section, "--salt", str(_RUN_SALT)]
    if on_cpu:
        cmd.append("--cpu")
    if no_cache:
        cmd.append("--nocache")
    if verify:
        cmd.append("--verify")
    if params is not None:
        cmd += ["--params", json.dumps(params)]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"[bench] section {section}: timed out after {timeout_s}s", file=sys.stderr)
        return None
    sys.stderr.write(out.stderr)
    if out.returncode != 0 or not out.stdout.strip():
        print(f"[bench] section {section}: rc={out.returncode}", file=sys.stderr)
        return None
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except json.JSONDecodeError:
        return None


# ------------------------------------------------------------ orchestration --


class _AccState:
    def __init__(self):
        self.failures = 0
        self.succeeded = False
        self.backend = None

    @property
    def dead(self) -> bool:
        # an early success does NOT exempt later failures from the
        # budget: a backend that dies mid-run must not burn 480s on every
        # remaining section
        return self.failures >= _MAX_ACC_FAILURES


# one implementation, shared with the obs registry and the watchdog
_apply_gates = gates.apply_gates
_UNIT_KEY = gates.UNIT_KEY


def _run_section_auto(section: str, acc: _AccState) -> tuple[dict | None, str]:
    """Accelerator first (subject to the failure budget), with digest
    verification against a CPU-pinned recompute; XLA:CPU fallback.
    Returns (fragment, 'accelerator'|'cpu'|'none')."""
    attempts: list[bool] = []
    if not acc.dead:
        attempts.append(False)
        # a corrupt persistent-cache entry must not hang every attempt:
        # retry the FIRST section once more bypassing the cache
        if not acc.succeeded and acc.failures == 0:
            attempts.append(True)
    for no_cache in attempts:
        frag = _section_in_subprocess(
            section,
            on_cpu=False,
            timeout_s=_section_timeout(section, _ACC_TIMEOUT_S),
            no_cache=no_cache,
        )
        if frag is not None and frag.get("backend") not in (None, "cpu"):
            # correctness coupling: tree verifies in-child (native sha);
            # epoch/resident/das against a CPU-pinned recompute of the
            # same salted inputs
            if "digest" in frag and "verified" not in frag:
                exp = _section_in_subprocess(
                    section,
                    on_cpu=True,
                    timeout_s=_VERIFY_TIMEOUT_S,
                    verify=True,
                    params=frag.get("params"),
                )
                if exp is None:
                    # the VERIFY recompute itself failed or timed out —
                    # the device measurement is unusable (unverifiable)
                    # but this says nothing about the device's health
                    print(
                        f"[bench] section {section}: host verify recompute "
                        "failed/timed out; discarding the (unverifiable) "
                        "device measurement",
                        file=sys.stderr,
                    )
                    break
                frag["verified"] = gates.digests_match(exp.get("digest"), frag["digest"])
            if not frag.get("verified"):
                print(
                    f"[bench] section {section}: REFUSED — device result does "
                    "not match the host recompute on the same inputs; the "
                    "device did not do the work being timed",
                    file=sys.stderr,
                )
                acc.failures += 1
                continue
            frag = _apply_gates(section, frag, _UNIT_KEY[section])
            if not frag.get("roofline_ok", True):
                # a verified result at an impossible rate still means the
                # TIMING is not a real execution time — never publish it
                acc.failures += 1
                continue
            acc.succeeded = True
            acc.backend = frag["backend"]
            return frag, "accelerator"
        if frag is not None:
            print(
                f"[bench] section {section}: accelerator attempt executed on "
                f"backend={frag.get('backend')!r}; treating as fallback",
                file=sys.stderr,
            )
        acc.failures += 1
        if acc.dead:
            break
    frag = _section_in_subprocess(section, on_cpu=True, timeout_s=_cpu_timeout(section))
    if frag is not None and "verified" not in frag:
        frag["verified"] = "same-backend (CPU lane; coupling applies to accelerator runs)"
    return frag, ("cpu" if frag is not None else "none")


def _load_lkg() -> dict | None:
    try:
        with open(_LKG_PATH) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


def _store_lkg(section_updates: dict) -> None:
    """Merge accelerator-measured numbers into BENCH_LKG.json. Only
    VERIFIED, roofline-sane entries are ever stored; provenance is per
    section."""
    cur = _load_lkg() or {}
    sections = cur.setdefault("sections", {})
    now = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    for name, entry in section_updates.items():
        entry["measured_utc"] = now
        sections[name] = entry
    cur["note"] = (
        "last-known-good ACCELERATOR measurements; every entry was "
        "correctness-coupled (device result == host recompute on the same "
        "inputs) and within the single-chip roofline when recorded"
    )
    try:
        tmp = _LKG_PATH + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(cur, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, _LKG_PATH)
    except OSError as e:
        print(f"[bench] could not update BENCH_LKG.json: {e}", file=sys.stderr)


def _fmt_rate(hps: float) -> str:
    return f"{hps/1e9:.3f} Ghash/s" if hps >= 1e8 else f"{hps/1e6:.2f} Mhash/s"


def xprof_capture() -> dict:
    """Targeted XLA attribution (obs/xprof.py) of the flagship kernels on
    THIS process's backend: AOT compile timing + executable memory for
    one sha256 tile and one merkle depth. Feeds the round's ``xprof``
    section, which scripts/perf_track.py ingests as non-gating secondary
    advisories (compile-time / memory blow-ups surface on the same
    same-platform timeline as throughput). ``ETH_SPECS_OBS_XPROF=0``
    skips it; any failure degrades to an empty section."""
    if os.environ.get("ETH_SPECS_OBS_XPROF", "1") in ("0", "false"):
        return {}
    try:
        import jax
        import jax.numpy as jnp

        from eth_consensus_specs_tpu.obs import xprof
        from eth_consensus_specs_tpu.ops import merkle as _mk
        from eth_consensus_specs_tpu.ops import sha256 as _sh

        tile = _sh.TILES[-1]  # the small fixed tile: bounded compile cost
        depth = 10
        captures = (
            xprof.analyze(
                "sha256", _sh._kernel,
                (jax.ShapeDtypeStruct((tile, 16), jnp.uint32),),
                hand_bytes=96 * tile, dims=(tile,), force=True,
            ),
            xprof.analyze(
                "merkle", _mk._tree_root_fused,
                (jax.ShapeDtypeStruct((1 << depth, 8), jnp.uint32), depth),
                hand_bytes=96 * _mk.tree_real_hashes(depth), dims=(depth,),
                force=True,
            ),
        )
        out: dict = {}
        for cap in captures:
            if not cap:
                continue
            name = cap["kernel"]
            if "compile_ms" in cap:
                out[f"{name}_compile_ms"] = cap["compile_ms"]
            if "peak_bytes" in cap:
                out[f"{name}_peak_bytes"] = cap["peak_bytes"]
        return out
    except Exception:
        return {}


def main() -> None:
    if "--section" in sys.argv:
        _child_main(sys.argv)
        return

    error = None
    dev_hps = 0.0
    host_hps = host_hashes_per_sec()
    nat_hps = native_hashes_per_sec()
    print(f"[bench] host hashlib: {host_hps/1e6:.2f} Mhash/s", file=sys.stderr)
    if nat_hps:
        print(f"[bench] host native sha core: {nat_hps/1e6:.2f} Mhash/s", file=sys.stderr)

    acc = _AccState()
    platforms: dict[str, str] = {}

    # the first accelerator ATTEMPT is the probe — full section budget, on
    # the real workload, with a --nocache retry
    tree, src = _run_section_auto("tree", acc)
    platforms["tree"] = src
    if tree is not None:
        dev_hps, tree_s = tree["hps"], tree["tree_s"]
        print(
            f"[bench] device tree (2^{tree['depth']} chunks, {src}, "
            f"verified={tree['verified']}): {_fmt_rate(dev_hps)} logical "
            f"({_fmt_rate(tree['real_hps'])} real full-width), "
            f"{tree_s*1e3:.1f} ms/tree",
            file=sys.stderr,
        )
    else:
        error = "device tree bench failed or timed out on every backend"

    epoch, src = _run_section_auto("epoch", acc)
    platforms["epoch"] = src
    if epoch is not None:
        print(
            f"[bench] fused epoch @{epoch['n']} validators ({src}, "
            f"verified={epoch['verified']}): {epoch['epoch_s']*1e3:.1f} ms",
            file=sys.stderr,
        )

    resident, src = _run_section_auto("resident", acc)
    platforms["resident"] = src
    if resident is not None:
        print(
            f"[bench] device-resident epoch+FULL-state-root @{resident['n']} "
            f"validators ({src}, verified={resident['verified']}): "
            f"{resident['per_epoch_s']*1e3:.2f} ms/epoch incremental vs "
            f"{resident.get('per_epoch_full_s', 0)*1e3:.2f} ms/epoch full "
            f"({resident.get('incremental_root_speedup')}x, roots bit-identical; "
            f"{resident['epochs']} epochs chained)",
            file=sys.stderr,
        )

    blockep, src = _run_section_auto("block_epoch", acc)
    platforms["block_epoch"] = src
    if blockep is not None:
        print(
            f"[bench] BLOCK epoch @{blockep['n']} validators x "
            f"{blockep['atts']} atts/slot w/ per-slot dirty roots ({src}, "
            f"verified={blockep['verified']}): {blockep['epoch_s']*1e3:.1f} ms/epoch "
            f"({blockep['slot_ms']:.2f} ms/slot)",
            file=sys.stderr,
        )

    # BLS: the host-native path is the production default (native C
    # multi-Miller pairing); the hybrid device path is attempted when its
    # compiled chains are warm. Both use fresh messages per timed repeat.
    bls_res = _section_in_subprocess("bls", on_cpu=True, timeout_s=_CPU_TIMEOUT_S)
    platforms["bls"] = "host-native" if bls_res is not None else "none"
    if not acc.dead:
        import glob as _glob

        from eth_consensus_specs_tpu.utils.cache import cache_dir_path

        if _glob.glob(os.path.join(cache_dir_path(), "device_pairing_warm.*")) or _glob.glob(
            os.path.join(cache_dir_path(), "device_h2c_warm.*")
        ):
            dev_bls = _section_in_subprocess("bls", on_cpu=False, timeout_s=_ACC_TIMEOUT_S)
            used_device_stage = dev_bls is not None and (
                dev_bls.get("pairing") == "device-miller" or dev_bls.get("h2c") == "device"
            )
            if (
                dev_bls is not None
                and dev_bls.get("backend") not in (None, "cpu")
                and used_device_stage
                and dev_bls.get("cross_checked")
            ):
                if dev_bls["aggs_per_sec"] > (bls_res["aggs_per_sec"] if bls_res else 0.0):
                    bls_res = dev_bls
                    platforms["bls"] = "accelerator-hybrid"
                _store_lkg(
                    {
                        "bls": {
                            "aggs_per_sec": round(dev_bls["aggs_per_sec"], 1),
                            "pairing": dev_bls.get("pairing"),
                            "h2c": dev_bls.get("h2c"),
                            "backend": dev_bls.get("backend"),
                            "fresh_messages": True,
                            "verified": True,
                        }
                    }
                )
            elif dev_bls is None:
                acc.failures += 1
    if bls_res is not None:
        print(
            f"[bench] RLC batch verify ({bls_res['n']} aggregates, fresh messages, "
            f"{bls_res.get('pairing', 'host-native')}): "
            f"{bls_res['aggs_per_sec']:.1f} aggregates/s "
            f"({bls_res['batch_s']*1e3:.0f} ms/batch, one pairing)",
            file=sys.stderr,
        )

    das_res, src = _run_section_auto("das", acc)
    platforms["das"] = src
    if das_res is not None:
        print(
            f"[bench] DAS field FFT ({das_res['batch']}x{das_res['n']}-point batch, "
            f"{src}, verified={das_res['verified']}): "
            f"{das_res['ffts_per_sec']:.1f} FFTs/s "
            f"({das_res['round_s']*1e3:.1f} ms/batch-round)",
            file=sys.stderr,
        )

    on_acc = platforms.get("tree") == "accelerator" and bool(tree) and tree.get(
        "roofline_ok", True
    )
    if not on_acc and error is None:
        error = (
            "accelerator backend unavailable after "
            f"{acc.failures} full-budget attempts; primary metric measured on "
            "XLA:CPU fallback (NOT a device regression — see last_known_good)"
        )
        print(f"[bench] {error}", file=sys.stderr)

    result = {
        "metric": "ssz_merkle_tree_hashes_per_sec",
        "value": round(dev_hps, 0),
        "unit": "hash/s",
        "vs_baseline": round(dev_hps / host_hps, 2) if host_hps else 0.0,
        "platform": (acc.backend or "unknown") if on_acc else "cpu-fallback",
        "section_platforms": platforms,
        "method": (
            "chained-dependency timing (K data-dependent iterations in one jit), "
            "device result REQUIRED to match a host recompute of the same salted "
            "inputs, accelerator rates gated by a single-chip HBM roofline"
        ),
        "verification": {
            name: frag.get("verified")
            for name, frag in (
                ("tree", tree),
                ("epoch", epoch),
                ("resident", resident),
                ("block_epoch", blockep),
                ("bls", bls_res),
                ("das", das_res),
            )
            if frag is not None
        },
        "secondary": {
            "host_hashlib_hashes_per_sec": round(host_hps, 0),
            "host_native_sha_hashes_per_sec": round(nat_hps, 0) if nat_hps else None,
            "tree_real_hashes_per_sec": round(tree["real_hps"], 0) if tree else None,
            "bls_aggregates_per_sec": round(bls_res["aggs_per_sec"], 1) if bls_res else None,
            "resident_epoch_plus_root_ms": (
                round(resident["per_epoch_s"] * 1e3, 3) if resident else None
            ),
            "resident_epoch_plus_root_full_ms": (
                round(resident["per_epoch_full_s"] * 1e3, 3)
                if resident and resident.get("per_epoch_full_s")
                else None
            ),
            "incremental_root_speedup": (
                resident.get("incremental_root_speedup") if resident else None
            ),
            "block_epoch_s": round(blockep["epoch_s"], 4) if blockep else None,
            "fused_epoch_ms": round(epoch["epoch_s"] * 1e3, 3) if epoch else None,
            "das_ffts_per_sec": round(das_res["ffts_per_sec"], 1) if das_res else None,
        },
    }

    # persist verified, roofline-sane accelerator numbers
    acc_update: dict = {}
    if platforms.get("tree") == "accelerator" and tree and tree.get("roofline_ok"):
        acc_update["tree"] = {
            "ssz_merkle_tree_hashes_per_sec": round(dev_hps, 0),
            "real_hashes_per_sec": round(tree["real_hps"], 0),
            "implied_gbps": tree.get("implied_gbps"),
            "vs_host_hashlib": round(dev_hps / host_hps, 2) if host_hps else None,
            "backend": tree.get("backend"),
            "verified": True,
        }
    if platforms.get("epoch") == "accelerator" and epoch and epoch.get("roofline_ok"):
        acc_update["epoch"] = {
            "fused_epoch_ms": round(epoch["epoch_s"] * 1e3, 3),
            "implied_gbps": epoch.get("implied_gbps"),
            "backend": epoch.get("backend"),
            "verified": True,
        }
    if platforms.get("resident") == "accelerator" and resident and resident.get("roofline_ok"):
        acc_update["resident"] = {
            "resident_epoch_plus_root_ms": round(resident["per_epoch_s"] * 1e3, 3),
            "resident_epoch_plus_root_full_ms": (
                round(resident["per_epoch_full_s"] * 1e3, 3)
                if resident.get("per_epoch_full_s")
                else None
            ),
            "incremental_root_speedup": resident.get("incremental_root_speedup"),
            "incremental_identical": resident.get("identical"),
            "implied_gbps": resident.get("implied_gbps"),
            "backend": resident.get("backend"),
            "verified": True,
        }
    if platforms.get("das") == "accelerator" and das_res and das_res.get("roofline_ok"):
        acc_update["das"] = {
            "das_ffts_per_sec": round(das_res["ffts_per_sec"], 1),
            "implied_gbps": das_res.get("implied_gbps"),
            "backend": das_res.get("backend"),
            "verified": True,
        }
    if platforms.get("block_epoch") == "accelerator" and blockep and blockep.get("roofline_ok"):
        acc_update["block_epoch"] = {
            "block_epoch_s": round(blockep["epoch_s"], 4),
            "n": blockep["n"],
            "atts_per_slot": blockep["atts"],
            "implied_gbps": blockep.get("implied_gbps"),
            "backend": blockep.get("backend"),
            "verified": True,
        }
    if acc_update:
        _store_lkg(acc_update)
    if not on_acc:
        lkg = _load_lkg()
        if lkg is not None:
            result["last_known_good"] = lkg
    xsec = xprof_capture()
    if xsec:
        result["xprof"] = xsec
    if error is not None:
        result["error"] = error
    print(json.dumps(result))


if __name__ == "__main__":
    main()
