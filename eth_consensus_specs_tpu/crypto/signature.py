"""BLS signature scheme (minimal-pubkey-size: pubkeys in G1, signatures in
G2), the construction the consensus spec relies on.

API parity with the verbs the reference's backend switch exposes
(reference: tests/core/pyspec/eth2spec/utils/bls.py:141-221): Sign, Verify,
Aggregate, AggregateVerify, FastAggregateVerify, AggregatePKs, KeyValidate,
SkToPk. Byte formats are the standard 48/96-byte compressed encodings.
"""

from __future__ import annotations

import threading

from .curve import (
    Point,
    g1_from_bytes,
    g1_generator,
    g1_infinity,
    g1_to_bytes,
    g2_from_bytes,
    g2_infinity,
    g2_to_bytes,
    in_subgroup,
)
from .fields import R
from .hash_to_curve import hash_to_g2
from .pairing import pairing_check


def sk_to_pk(sk: int) -> bytes:
    if not 0 < sk < R:
        raise ValueError("secret key out of range")
    return g1_to_bytes(g1_generator().mul(sk))


def sign(sk: int, message: bytes) -> bytes:
    if not 0 < sk < R:
        raise ValueError("secret key out of range")
    return g2_to_bytes(hash_to_g2(message).mul(sk))


def key_validate(pk_bytes: bytes) -> bool:
    """Valid compressed encoding, on curve, in subgroup, not infinity."""
    try:
        p = g1_from_bytes(bytes(pk_bytes))
    except ValueError:
        return False
    return not p.is_infinity()


# Pubkey decompression (sqrt + subgroup check) is the per-operation fixed
# cost of every verification, and validator pubkeys repeat constantly —
# the reference leans on milagro doing this in C; we add a bounded cache on
# top of the native path (same effect as the reference's LRU-cached
# committee pipelines keeping pk objects alive). The bound holds a mainnet
# registry twice over, and a full cache drops its OLDEST key: a block
# carries 2^16 keys of a registry that cycles in 32 blocks, so a cache
# that held 2^16 and cleared itself when full decompressed every block's
# keys again. (A service that was handed its registry does not come here:
# ops/key_table.py.)
_PK_CACHE: dict[bytes, Point | None] = {}
_PK_CACHE_MAX = 1 << 21
# keys the calling thread has decompressed so far (cache misses), in
# `.count`: read before and after a batch by a caller that wants to know
# whether the batch decoded any
_PK_DECODES = threading.local()


def pk_decodes() -> int:
    return getattr(_PK_DECODES, "count", 0)


def _load_pk(pk_bytes: bytes) -> Point | None:
    from eth_consensus_specs_tpu.crypto import native_bridge as nb

    key = bytes(pk_bytes)
    # the cache holds natively-decompressed points; consulting it with the
    # bridge disabled would let cached native results masquerade as the
    # pure-Python oracle in cross-check tests
    use_cache = nb.enabled()
    if use_cache:
        hit = _PK_CACHE.get(key, False)
        if hit is not False:
            return hit
    _PK_DECODES.count = pk_decodes() + 1
    try:
        p = g1_from_bytes(key)
    except ValueError:
        p = None
    if p is not None and p.is_infinity():
        p = None
    if use_cache:
        if len(_PK_CACHE) >= _PK_CACHE_MAX:
            del _PK_CACHE[next(iter(_PK_CACHE))]
        _PK_CACHE[key] = p
    return p


def _load_sig(sig_bytes: bytes) -> Point | None:
    try:
        return g2_from_bytes(bytes(sig_bytes))
    except ValueError:
        return None


def verify(pk_bytes: bytes, message: bytes, sig_bytes: bytes) -> bool:
    pk = _load_pk(pk_bytes)
    sig = _load_sig(sig_bytes)
    if pk is None or sig is None:
        return False
    g1 = g1_generator()
    return pairing_check([(pk, hash_to_g2(bytes(message))), (-g1, sig)])


def _sum_g2(points: list[Point]) -> Point:
    from eth_consensus_specs_tpu.crypto import native_bridge as nb
    from .fields import Fq, Fq2
    from .curve import B2, Point as _P

    if nb.enabled():
        raw = nb.g2_aggregate(
            [
                None
                if p.is_infinity()
                else ((p.x.c0.n, p.x.c1.n), (p.y.c0.n, p.y.c1.n))
                for p in points
            ]
        )
        if raw is None:
            return g2_infinity()
        (x0, x1), (y0, y1) = raw
        return _P(Fq2(Fq(x0), Fq(x1)), Fq2(Fq(y0), Fq(y1)), B2)
    acc = g2_infinity()
    for p in points:
        acc = acc + p
    return acc


def _sum_g1(points: list[Point]) -> Point:
    from eth_consensus_specs_tpu.crypto import native_bridge as nb
    from .fields import Fq
    from .curve import B1, Point as _P

    if nb.enabled():
        raw = nb.g1_aggregate(
            [None if p.is_infinity() else (p.x.n, p.y.n) for p in points]
        )
        if raw is None:
            return g1_infinity()
        return _P(Fq(raw[0]), Fq(raw[1]), B1)
    acc = g1_infinity()
    for p in points:
        acc = acc + p
    return acc


def aggregate(signatures: list[bytes]) -> bytes:
    if len(signatures) == 0:
        raise ValueError("cannot aggregate zero signatures")
    points = []
    for s in signatures:
        p = _load_sig(s)
        if p is None:
            raise ValueError("invalid signature in aggregate")
        points.append(p)
    return g2_to_bytes(_sum_g2(points))


def aggregate_pks(pubkeys: list[bytes]) -> bytes:
    if len(pubkeys) == 0:
        raise ValueError("cannot aggregate zero pubkeys")
    points = []
    for pk in pubkeys:
        p = _load_pk(pk)
        if p is None:
            raise ValueError("invalid pubkey in aggregate")
        points.append(p)
    return g1_to_bytes(_sum_g1(points))


def aggregate_verify(pks: list[bytes], messages: list[bytes], sig_bytes: bytes) -> bool:
    if len(pks) != len(messages) or len(pks) == 0:
        return False
    sig = _load_sig(sig_bytes)
    if sig is None:
        return False
    pairs = []
    for pk_b, msg in zip(pks, messages):
        pk = _load_pk(pk_b)
        if pk is None:
            return False
        pairs.append((pk, hash_to_g2(bytes(msg))))
    pairs.append((-g1_generator(), sig))
    return pairing_check(pairs)


def fast_aggregate_verify(pks: list[bytes], message: bytes, sig_bytes: bytes) -> bool:
    if len(pks) == 0:
        return False
    points = []
    for pk_b in pks:
        pk = _load_pk(pk_b)
        if pk is None:
            return False
        points.append(pk)
    acc = _sum_g1(points)
    sig = _load_sig(sig_bytes)
    if sig is None:
        return False
    return pairing_check([(acc, hash_to_g2(bytes(message))), (-g1_generator(), sig)])
