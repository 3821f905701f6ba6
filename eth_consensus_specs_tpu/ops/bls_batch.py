"""Batched BLS verification — the device-backend seam.

The consensus workload's signature hot spot is many independent
FastAggregateVerify calls per block (<=128 attestations x committee
aggregates; reference call sites: specs/phase0/beacon-chain.md:776-792,
specs/altair/beacon-chain.md:575-650). The batching seams:

  1. aggregate pubkey sums + RLC scalar products run as a DEVICE G1 MSM
     (ops/g1_msm limb kernel) when the tpu backend is selected;
  2. random-linear-combination batching collapses N pairing checks into
     one (the algorithmic seam the reference uses for KZG batches,
     specs/deneb/polynomial-commitments.md:412-463);
  3. the Miller accumulation and final-exponentiation membership check
     run on DEVICE too (ops/pairing_device — host prepares per-Q line
     coefficients, the device runs the batched fixed-structure loop);
     only hash-to-curve and the 64-bit G2 RLC multiplies stay host-side.

`process_operations` routes block attestations through
`batch_verify_aggregates` (one pairing per block) and falls back to
per-attestation verification only when the batch rejects, so the invalid
attestation surfaces at the exact spec assertion.
"""

from __future__ import annotations

import contextlib
import functools
import os
import secrets
import threading
import time

import numpy as np

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.analysis import lockwatch
from eth_consensus_specs_tpu.crypto.curve import (
    B1,
    Point,
    g1_generator,
    g1_infinity,
    g2_from_bytes,
)
from eth_consensus_specs_tpu.crypto.hash_to_curve import DST_G2, hash_to_g2
from eth_consensus_specs_tpu.crypto.pairing import pairing_check
from eth_consensus_specs_tpu.obs import watchdog, waterfall


def _use_device() -> bool:
    # snapshot the backend switch ONCE per batch (callers read it a single
    # time and thread the answer through): a concurrent use_tpu()/
    # use_pyspec() flip mid-batch must not route half a batch's items
    # through each backend
    from eth_consensus_specs_tpu.utils import bls

    return bls.backend_name() == "tpu"


# hash-to-G2 results keyed by (dst, message): `_rlc_check` primes a
# check's distinct messages, `_h2g2` hashes on the host per miss. The
# dst is part of the key so a caller priming under one domain
# can never serve a point to a reader under another.  All mutation holds
# _H2G2_LOCK: the serving layer's micro-batcher verifies off-thread, and
# an unlocked evict (clear + update) racing a concurrent prime could
# publish a half-rebuilt dict.
_H2G2_CACHE: dict[tuple[bytes, bytes], object] = {}
_H2G2_LOCK = lockwatch.wrap(threading.Lock(), "ops.bls_batch._H2G2_LOCK")


def _reinit_lock_after_fork_in_child() -> None:
    # fork-safety: the serving layer's batch thread primes this cache
    # off-thread; a gen-pool fork mid-prime must not hand the child a
    # held lock (the cache contents are read-only-safe to inherit)
    global _H2G2_LOCK
    _H2G2_LOCK = lockwatch.wrap(threading.Lock(), "ops.bls_batch._H2G2_LOCK")


os.register_at_fork(after_in_child=_reinit_lock_after_fork_in_child)


def _prime_h2g2_cache(msgs: list[bytes], batch_fn, dst: bytes = DST_G2) -> None:
    # evict BEFORE deciding what to batch: clearing afterwards would drop
    # this very call's cached messages and push them onto the serial host
    # path — the opposite of what the batched dispatch is for
    keys = [(dst, m) for m in msgs]
    with _H2G2_LOCK:
        if len(_H2G2_CACHE) + len(keys) > 512:
            keep = {k: _H2G2_CACHE[k] for k in keys if k in _H2G2_CACHE}
            _H2G2_CACHE.clear()
            _H2G2_CACHE.update(keep)
        fresh = [m for m in msgs if (dst, m) not in _H2G2_CACHE]
    if not fresh:
        return
    # the batched dispatch runs OUTSIDE the lock (it can be slow; two
    # racing primes at worst both compute — idempotent, never corrupt)
    points = batch_fn(fresh, dst)
    with _H2G2_LOCK:
        # re-check the bound at insert time: N racing primes could each
        # have passed the pre-dispatch check, and unbounded overshoot
        # would defeat the cap (evicting here keeps THIS call's keys)
        if len(_H2G2_CACHE) + len(fresh) > 512:
            keep = {k: _H2G2_CACHE[k] for k in keys if k in _H2G2_CACHE}
            _H2G2_CACHE.clear()
            _H2G2_CACHE.update(keep)
        for m, p in zip(fresh, points):
            _H2G2_CACHE[(dst, m)] = p


def _h2g2(msg: bytes, dst: bytes = DST_G2):
    with _H2G2_LOCK:
        hit = _H2G2_CACHE.get((dst, msg))
    return hit if hit is not None else hash_to_g2(msg, dst)


def _pairing_check_routed(pairs, mesh=None) -> bool:
    """Device Miller loop + membership check under the tpu backend
    (``bls.use_tpu()``); the host/native pairing elsewhere. Both are
    bit-equivalent implementations of the same check
    (tests/test_pairing_device.py), so routing can never flip a
    verification result."""
    if _use_device():
        from eth_consensus_specs_tpu.ops.pairing_device import pairing_check_device

        return pairing_check_device(pairs, mesh=mesh)
    return pairing_check(pairs)


def fast_aggregate_verify_device(pks: list[bytes], message: bytes, sig: bytes) -> bool:
    """FastAggregateVerify with the pubkey aggregation on device and the
    pairing on host. Semantics mirror the host path exactly (per-key
    validation rejects infinity KEYS, but an infinity AGGREGATE proceeds
    into the pairing — crypto/signature.py:115-127) so backend choice can
    never flip a verification result."""
    from eth_consensus_specs_tpu.crypto.signature import _load_pk, _load_sig
    from eth_consensus_specs_tpu.ops.g1_msm import sum_g1_device

    if len(pks) == 0:
        return False
    points = []
    for pk_b in pks:
        pk = _load_pk(bytes(pk_b))
        if pk is None:
            return False
        points.append(pk)
    sig_pt = _load_sig(bytes(sig))
    if sig_pt is None:
        return False
    with obs.span("bls.fast_aggregate_verify", pubkeys=len(pks)):
        obs.count("bls.fast_aggregate_verifies", 1)
        obs.count("bls.pubkeys_aggregated", len(pks))
        aggpk = sum_g1_device(points)
        return _pairing_check_routed(
            [(aggpk, hash_to_g2(bytes(message))), (-g1_generator(), sig_pt)]
        )


def batch_verify_aggregates(
    items: list[tuple[list[bytes], bytes, bytes]], mesh=None
) -> bool:
    """Verify many (pubkeys, message, aggregate_signature) triples with ONE
    pairing check via random linear combination:

        prod_i e(r_i * aggpk_i, H(m_i)) * e(-G1, sum_i r_i * sig_i) == 1

    Sound: a forged triple passes only with probability ~1/2^64 over the
    random r_i. With the tpu backend each item's committee pubkeys sum in
    the device pairwise-sum kernel (one dispatch per item; the compiled
    executable is shared across same-pow2 committee sizes) and the 64-bit
    r_i multiply happens host-side on the single aggregate point; the G2
    side (hash-to-curve, memoized per distinct message) and the final
    pairing are host-side.
    """
    if not items:
        return True
    with obs.span("bls.batch_verify", items=len(items)):
        obs.count("bls.batches", 1)
        obs.count("bls.batch_items", len(items))
        ok, parsed = _batch_verify_impl(items, mesh=mesh)
    # the watchdog's host-pairing recompute runs AFTER the span closes
    # (like sha256/merkle/shuffle): the probe must never be clocked as
    # kernel time in the obs report
    if ok and parsed and watchdog.should_check("bls_batch"):
        # a True batch verdict must reproduce for any member item through
        # the plain host pairing (no device MSM, no routed pairing, no
        # h2g2 cache) — the sampled item rotates with the call counter
        points, msg, sig, _r = parsed[watchdog.call_salt("bls_batch") % len(parsed)]
        watchdog.check_bls_item(points, msg, sig, ok)
    return ok


def _parse_item(item: tuple, keys=None):
    """(signers, message, signature) -> (signers, msg, sig, r), or None on
    any malformed/empty input. The signers come as 48-byte public keys
    or as an array of registry indices; with the registry's key table
    (``ops/key_table.KeyTable``) both resolve to an index array, without
    one the keys are decoded to points (cached: ``signature._load_pk``
    refuses malformed AND infinity keys)."""
    pks, msg, sig_b = item
    if len(pks) == 0:
        return None
    signers = keys.resolve(pks) if keys is not None else None
    if signers is None:
        if isinstance(pks, np.ndarray):
            return None  # indices into a registry this caller was not given
        signers = _decode_keys(pks)
        if signers is None:
            return None
    try:
        sig = g2_from_bytes(bytes(sig_b))
    except ValueError:
        return None
    r = secrets.randbits(64) | 1
    return (signers, bytes(msg), sig, r)


def _decode_keys(pks: list) -> list | None:
    from eth_consensus_specs_tpu.crypto.signature import _load_pk

    points = []
    for pk in pks:
        p = _load_pk(bytes(pk))
        if p is None:
            return None
        points.append(p)
    return points


@contextlib.contextmanager
def _decode_clock():
    """One sample of ``bls.key_decode_ms`` where the block decompressed a
    key on this thread."""
    from eth_consensus_specs_tpu.crypto import signature

    decodes, t0 = signature.pk_decodes(), time.perf_counter()
    yield
    if signature.pk_decodes() != decodes:
        obs.observe("bls.key_decode_ms", (time.perf_counter() - t0) * 1e3)


def warm_keys(items: list) -> None:
    """Decode the byte-form keys of a flush into ``signature._load_pk``'s
    cache ahead of its dispatch: a service without a key table does it in
    host prep, overlapped with the flush before."""
    with _decode_clock():
        for pks, _, _ in items:
            if not isinstance(pks, np.ndarray):
                _decode_keys(pks)


def _parse_flush(items: list, keys=None) -> list:
    with _decode_clock():
        return [_parse_item(it, keys) for it in items]


def _signer_points(signers, keys) -> list:
    return keys.points(signers) if isinstance(signers, np.ndarray) else signers


def _batch_verify_impl(
    items: list[tuple[list[bytes], bytes, bytes]],
    mesh=None,
) -> tuple[bool, list | None]:
    parsed = []
    for item in items:
        p = _parse_item(item)
        if p is None:
            return False, None
        parsed.append(p)
    rpk = _rlc_pubkey_terms(parsed, mesh=mesh)
    pairing = functools.partial(_pairing_check_routed, mesh=mesh)
    return _rlc_check(parsed, rpk, pairing), parsed


def _rlc_pubkey_terms(parsed: list, mesh=None) -> list:
    """Per-item r_i * aggpk_i — independent of which subset of the batch
    a later check verifies, so verify_many's bisection computes these
    ONCE per item and re-checks subsets with only the G2 MSM + pairing."""
    if not parsed:
        return []
    if _use_device():
        from eth_consensus_specs_tpu.ops.g1_msm import sum_g1_many_device
        from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count
        from eth_consensus_specs_tpu.serve import buckets

        # the scalar is uniform within an item, so r_i * aggpk_i factors
        # to r_i * sum(points): ONE batched device dispatch sums every
        # item's committee (item axis sharded over `mesh` when live),
        # and the single 64-bit host multiply per item replaces an
        # n-lane 256-bit double-and-add. The dispatch shape/key is the
        # LIVE serve key fn (serve/buckets.bls_msm_key — the same
        # callable jaxlint's recompile-surface check exercises); its
        # first sighting is the compile this process pays for that
        # (items, lanes[, mesh]) key — accounted here so serve and
        # direct callers agree.
        shards = shard_count(mesh)
        key = buckets.bls_msm_key(
            len(parsed), max(len(points) for points, _, _, _ in parsed), mesh=mesh
        )
        with buckets.first_dispatch(*key):
            sums = sum_g1_many_device(
                [points for points, _, _, _ in parsed],
                mesh=mesh if shards > 1 else None,
                pad_shape=(key[1], key[2]),
            )
        rpk = [s.mul(r) for s, (_, _, _, r) in zip(sums, parsed)]
    else:
        from eth_consensus_specs_tpu.crypto import native_bridge as nb
        from eth_consensus_specs_tpu.crypto.fields import Fq

        rpk = []
        native = nb.enabled()
        for points, _, _, r in parsed:
            if native:
                # one C call sums the committee (vs n affine adds, each a
                # field inversion round-trip through the bridge)
                raw = nb.g1_aggregate(
                    [None if p.is_infinity() else (p.x.n, p.y.n) for p in points]
                )
                aggpk = (
                    g1_infinity()
                    if raw is None
                    else Point(Fq(raw[0]), Fq(raw[1]), points[0].b)
                )
            else:
                aggpk = g1_infinity()
                for p in points:
                    aggpk = aggpk + p
            rpk.append(aggpk.mul(r))
    return rpk


# == the served path: each leg where what the code observes puts it =========
#
# `verify_many` reads no backend switch and no environment variable. What
# it observes: whether the caller handed over a key table and a mesh,
# whether the flush's (items, lanes) bucket has its program compiled, and
# whether it runs on a service's thread. Measured on one v5e at a mainnet
# block's 128 aggregates x 512 keys (PERF.md section 5), the committee
# sums are the one leg the device does faster than the C core (68 ms
# against 105 ms, the keys resident on both sides), and its program takes
# ~200 s to compile. So the sums go to the device, one or the mesh handed
# in (item axis sharded), for a bucket that `serve/buckets.precompile` or
# an earlier dispatch has compiled. A bucket not yet compiled goes through
# the core on a service's thread, where a compile would hold every request
# behind it for minutes; off it, a caller who handed a mesh asked for the
# devices and waits for the compile itself. Hash-to-G2 and the pairing
# have device programs that lose at a block's shape (or cannot be built on
# the chip's host) and the G2 fold has none: those legs take the core.


def _takes_device(key: tuple, mesh) -> bool:
    from eth_consensus_specs_tpu.serve import buckets
    from eth_consensus_specs_tpu.serve.service import on_service_thread

    return buckets.is_compiled(*key) or (mesh is not None and not on_service_thread())


def _host_sum(signers, keys) -> Point:
    """A committee's sum on the host: straight from the table's affine
    rows in the C core where the signers are registry indices."""
    from eth_consensus_specs_tpu.crypto import native_bridge as nb
    from eth_consensus_specs_tpu.crypto.fields import Fq
    from eth_consensus_specs_tpu.crypto.signature import _sum_g1

    if isinstance(signers, np.ndarray) and nb.enabled():
        raw = nb.g1_aggregate_affine(keys.affine[signers].tobytes())
        return g1_infinity() if raw is None else Point(Fq(raw[0]), Fq(raw[1]), B1)
    return _sum_g1(_signer_points(signers, keys))


def _served_pubkey_terms(parsed: list, keys=None, mesh=None) -> list:
    """Per-item r_i * aggpk_i of a served flush. Signers the key table
    resolved to registry indices are gathered and summed from the table's
    limbs, signers that came as points of their own are packed and summed
    over a mesh only; on the device where `_takes_device` says so, through
    the C core otherwise."""
    from eth_consensus_specs_tpu.ops import g1_msm
    from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count
    from eth_consensus_specs_tpu.serve import buckets

    if shard_count(mesh) <= 1:
        mesh = None
    indexed = [i for i, p in enumerate(parsed) if isinstance(p[0], np.ndarray)]
    loose = [i for i, p in enumerate(parsed) if not isinstance(p[0], np.ndarray)]
    sums: list = [None] * len(parsed)
    jacobian = None
    with waterfall.leg("bls.g1_sum.call"):
        if indexed:
            rows = [parsed[i][0] for i in indexed]
            key = buckets.bls_keysum_key(len(rows), max(map(len, rows)), len(keys), mesh=mesh)
            if _takes_device(key, mesh):
                with buckets.first_dispatch(*key):
                    jacobian = g1_msm.sum_indexed_device(
                        keys.device_limbs(mesh), rows, key[1:3], mesh=mesh
                    )
            else:
                for i in indexed:
                    sums[i] = _host_sum(parsed[i][0], keys)
        lists = [parsed[i][0] for i in loose]
        key = None
        if lists and mesh is not None:
            key = buckets.bls_msm_key(len(lists), max(map(len, lists)), mesh=mesh)
        if key is not None and _takes_device(key, mesh):
            with buckets.first_dispatch(*key):
                device_sums = g1_msm.sum_g1_many_device(lists, mesh=mesh, pad_shape=key[1:3])
            for i, s in zip(loose, device_sums):
                sums[i] = s
        else:
            for i in loose:
                sums[i] = _host_sum(parsed[i][0], keys)
    with waterfall.leg("bls.g1_sum.unpack"):
        if jacobian is not None:
            for i, point in zip(indexed, g1_msm._jacobian_to_points(*jacobian)):
                sums[i] = point
        return [s.mul(p[3]) for s, p in zip(sums, parsed)]


def _host_hash_many(msgs: list[bytes], dst: bytes) -> list:
    return [hash_to_g2(m, dst) for m in msgs]


def _merge_by_message(parsed: list, rpk: list) -> dict:
    """Same-message items merged into one pairing input (block
    attestations often share AttestationData): k items with m distinct
    messages -> m+1 Miller loops instead of k+1."""
    merged: dict[bytes, object] = {}
    for (_, msg, _, _), rp in zip(parsed, rpk):
        merged[msg] = rp if msg not in merged else merged[msg] + rp
    return merged


def _fold_signatures(parsed: list):
    """sum_i r_i * sig_i in ONE native Pippenger MSM (64-bit scalars are
    always < r, so the reduced path is exact); multi_exp falls back to
    the bit-exact per-point path without the native core."""
    from eth_consensus_specs_tpu.utils.bls import multi_exp

    return multi_exp([sig for _, _, sig, _ in parsed], [r for _, _, _, r in parsed])


def _rlc_check(parsed: list, rpk: list, pairing) -> bool:
    """One random-linear-combination pairing over a batch, a served flush
    or a subset of it. Hash-to-G2 and the G2 fold go through the C core
    (above), the pairs to the ``pairing`` callable the entry point hands
    in; each leg under its own clock, one sample of ``bls.rlc_check_ms``
    a check."""
    t0 = time.perf_counter()
    merged = _merge_by_message(parsed, rpk)
    with waterfall.leg("bls.h2c"):
        # kept for the subsets a bisection checks again and for the block
        # after this one
        _prime_h2g2_cache(list(merged), _host_hash_many)
        pairs = [(rp, _h2g2(msg)) for msg, rp in merged.items()]
    with waterfall.leg("bls.g2_fold"):
        pairs.append((-g1_generator(), _fold_signatures(parsed)))
    obs.count("bls.pairings", 1)
    obs.count("bls.pairing_inputs", len(pairs))
    obs.count("bls.messages_distinct", len(merged))
    with waterfall.leg("bls.pairing"):
        ok = pairing(pairs)
    obs.observe("bls.rlc_check_ms", (time.perf_counter() - t0) * 1e3)
    return ok


def verify_many(items: list[tuple], mesh=None, keys=None) -> list[bool]:
    """Per-item verdicts for many (signers, message, aggregate_signature)
    triples — the serving layer's batch entry point. The signers are
    48-byte public keys or, with the registry's key table in ``keys``, an
    array of registry indices. Parsing and the per-item G1 terms are
    computed ONCE; one RLC pairing settles an all-valid batch (the
    overwhelmingly common case), and a reject bisects with only the G2
    MSM + pairing per subset, so each invalid item costs ~2*log2(n)
    pairings instead of n.

    Each leg runs where `_served_pubkey_terms` and `_rlc_check` put it.
    With a multi-device ``mesh`` the per-item G1 terms shard their item axis (the indices where the signers are in the key table, the
    table replicated; the packed points otherwise); the terms are
    canonical affine points whichever side summed them, so the bisection
    re-checks subsets with the SAME terms and verdicts stay bit-identical
    on every routing and whatever the mesh shape.

    Per-item results are exactly what ``batch_verify_aggregates([item])``
    returns: a singleton RLC check is ``X^r == 1`` in the prime-order
    pairing group with odd 64-bit r, which holds iff ``X == 1`` — i.e.
    the singleton batch is deterministic, not probabilistic, so bisection
    verdicts are bit-identical to per-request direct calls."""
    if not items:
        return []
    with obs.span("bls.verify_many", items=len(items)):
        obs.count("bls.verify_many_items", len(items))
        out = [False] * len(items)
        with waterfall.leg("bls.keys"):
            parsed = _parse_flush(items, keys)
        live = [i for i, p in enumerate(parsed) if p is not None]
        if not live:
            return out
        sub = [parsed[i] for i in live]
        rpk = _served_pubkey_terms(sub, keys, mesh)
        verdicts = _bisect_rlc(sub, rpk)
        for i, v in zip(live, verdicts):
            out[i] = v
    # sampled device/host coupling on the serving path too (outside the
    # span, same as batch_verify_aggregates): one item's verdict must
    # reproduce through the plain host pairing
    if live and watchdog.should_check("bls_batch"):
        k = live[watchdog.call_salt("bls_batch") % len(live)]
        signers, msg, sig, _r = parsed[k]
        watchdog.check_bls_item(_signer_points(signers, keys), msg, sig, out[k])
    return out


def _bisect_rlc(parsed: list, rpk: list) -> list[bool]:
    if _rlc_check(parsed, rpk, pairing_check):
        return [True] * len(parsed)
    if len(parsed) == 1:
        return [False]
    mid = len(parsed) // 2
    return _bisect_rlc(parsed[:mid], rpk[:mid]) + _bisect_rlc(parsed[mid:], rpk[mid:])
