"""BLS12-381 signature-scheme tests: scheme consistency, serialization
round-trips, negative cases, batch verification, and the backend switch."""

import functools

import pytest

from eth_consensus_specs_tpu.crypto.curve import (
    g1_from_bytes,
    g1_generator,
    g1_to_bytes,
    g2_from_bytes,
    g2_generator,
    g2_to_bytes,
    in_subgroup,
)
from eth_consensus_specs_tpu.ops.bls_batch import batch_verify_aggregates
from eth_consensus_specs_tpu.utils import bls


def setup_module():
    bls.bls_active = True


MSG_A = b"\x12" * 32
MSG_B = b"\x34" * 32


def test_sign_verify_roundtrip():
    sk = 12345
    pk = bls.SkToPk(sk)
    sig = bls.Sign(sk, MSG_A)
    assert bls.Verify(pk, MSG_A, sig)
    assert not bls.Verify(pk, MSG_B, sig)
    assert not bls.Verify(bls.SkToPk(999), MSG_A, sig)


def test_signature_deterministic():
    assert bls.Sign(7, MSG_A) == bls.Sign(7, MSG_A)
    assert bls.Sign(7, MSG_A) != bls.Sign(8, MSG_A)


def test_aggregate_and_fast_aggregate_verify():
    sks = [1, 2, 3]
    pks = [bls.SkToPk(sk) for sk in sks]
    sigs = [bls.Sign(sk, MSG_A) for sk in sks]
    agg = bls.Aggregate(sigs)
    assert bls.FastAggregateVerify(pks, MSG_A, agg)
    assert not bls.FastAggregateVerify(pks, MSG_B, agg)
    assert not bls.FastAggregateVerify(pks[:2], MSG_A, agg)


def test_aggregate_verify_distinct_messages():
    sks = [5, 6]
    msgs = [MSG_A, MSG_B]
    pks = [bls.SkToPk(sk) for sk in sks]
    agg = bls.Aggregate([bls.Sign(sk, m) for sk, m in zip(sks, msgs)])
    assert bls.AggregateVerify(pks, msgs, agg)
    assert not bls.AggregateVerify(pks, [MSG_A, MSG_A], agg)


def test_key_validate():
    assert bls.KeyValidate(bls.SkToPk(42))
    assert not bls.KeyValidate(bls.G1_POINT_AT_INFINITY)
    assert not bls.KeyValidate(b"\x00" * 48)
    assert not bls.KeyValidate(b"\xff" * 48)


def test_point_serialization_roundtrip():
    p = g1_generator().mul(777)
    assert g1_from_bytes(g1_to_bytes(p)) == p
    q = g2_generator().mul(888)
    assert g2_from_bytes(g2_to_bytes(q)) == q
    assert in_subgroup(q)


def test_invalid_signature_bytes_rejected():
    pk = bls.SkToPk(1)
    assert not bls.Verify(pk, MSG_A, b"\x00" * 96)
    assert not bls.Verify(pk, MSG_A, b"\xff" * 96)


def test_batch_verify_aggregates():
    sks1, sks2 = [1, 2], [3, 4]
    pks1 = [bls.SkToPk(s) for s in sks1]
    pks2 = [bls.SkToPk(s) for s in sks2]
    agg1 = bls.Aggregate([bls.Sign(s, MSG_A) for s in sks1])
    agg2 = bls.Aggregate([bls.Sign(s, MSG_B) for s in sks2])
    assert batch_verify_aggregates([(pks1, MSG_A, agg1), (pks2, MSG_B, agg2)])
    # one bad item poisons the batch
    assert not batch_verify_aggregates([(pks1, MSG_A, agg1), (pks2, MSG_A, agg2)])


def test_stub_mode():
    bls.bls_active = False
    try:
        assert bls.Sign(1, MSG_A) == bls.STUB_SIGNATURE
        assert bls.Verify(b"\x00" * 48, MSG_A, bls.STUB_SIGNATURE)
        assert bls.FastAggregateVerify([], MSG_A, bls.STUB_SIGNATURE)
    finally:
        bls.bls_active = True


def test_h2g2_cache_keys_include_dst():
    """Regression (ADVICE round-4 low): the hash-to-G2 cache must key on
    (dst, message) — a caller priming under one domain-separation tag
    must never serve its points to a reader under another."""
    from eth_consensus_specs_tpu.ops import bls_batch

    msg = b"\xaa" * 32
    dst_a, dst_b = b"DST-A", b"DST-B"
    saved = dict(bls_batch._H2G2_CACHE)
    bls_batch._H2G2_CACHE.clear()
    try:
        bls_batch._prime_h2g2_cache([msg], lambda ms, dst: ["A-point"] * len(ms), dst=dst_a)
        bls_batch._prime_h2g2_cache([msg], lambda ms, dst: ["B-point"] * len(ms), dst=dst_b)
        # both entries coexist — neither aliased the other
        assert bls_batch._h2g2(msg, dst_a) == "A-point"
        assert bls_batch._h2g2(msg, dst_b) == "B-point"
        assert (dst_a, msg) in bls_batch._H2G2_CACHE
        assert (dst_b, msg) in bls_batch._H2G2_CACHE
        # a third DST misses the cache entirely (falls through to a real
        # hash_to_g2 — a point object, never one of the sentinels)
        real = bls_batch._h2g2(msg, b"DST-C" + bls_batch.DST_G2)
        assert real not in ("A-point", "B-point")
    finally:
        bls_batch._H2G2_CACHE.clear()
        bls_batch._H2G2_CACHE.update(saved)


def test_batch_verify_emits_obs_counters(kernel_counters):
    from eth_consensus_specs_tpu import obs

    sks = [5, 6]
    pks = [bls.SkToPk(s) for s in sks]
    agg = bls.Aggregate([bls.Sign(s, MSG_A) for s in sks])
    assert batch_verify_aggregates([(pks, MSG_A, agg)])
    delta = kernel_counters()
    assert delta["bls.batches"] == 1
    assert delta["bls.batch_items"] == 1
    assert delta["bls.pairings"] == 1
    assert "bls.batch_verify" in obs.snapshot()["spans"]


# ------------------------------------------- one RLC check, two entry points --


def _agg_item(sks, msg):
    return ([bls.SkToPk(s) for s in sks], msg, bls.Aggregate([bls.Sign(s, msg) for s in sks]))


@functools.lru_cache(maxsize=None)
def _rlc_scenarios() -> dict:
    a, b, c = _agg_item([1, 2], MSG_A), _agg_item([3, 4], MSG_B), _agg_item([5], b"\x56" * 32)
    return {
        "all_valid": ([a, b, c], [True, True, True]),
        "wrong_signature": ([a, (b[0], b[1], c[2]), c], [True, False, True]),
        "wrong_message": ([a, (b[0], MSG_A, b[2]), c], [True, False, True]),
        "shared_message": ([a, _agg_item([3, 4], MSG_A), c], [True, True, True]),
        "malformed_signature": ([a, (b[0], b[1], b"\x01" + bytes(b[2])[1:]), c], [True, False, True]),
        "empty_signers": ([a, ([], b[1], b[2]), c], [True, False, True]),
    }


@pytest.mark.parametrize("entry", ["batch_verify_aggregates", "verify_many"])
@pytest.mark.parametrize("scenario", [
    "all_valid", "wrong_signature", "wrong_message", "shared_message",
    "malformed_signature", "empty_signers",
])
def test_one_rlc_check_behind_both_entry_points(scenario, entry, kernel_counters):
    """`batch_verify_aggregates` (the spec path, item by item) and
    `verify_many` (the served path) reach the SAME check: equal verdicts,
    and one sample of ``bls.rlc_check_ms`` a pairing from either."""
    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.ops import bls_batch

    def samples() -> int:
        return obs.snapshot()["histograms"].get("bls.rlc_check_ms", {}).get("count", 0)

    items, want = _rlc_scenarios()[scenario]
    before = samples()
    if entry == "verify_many":
        got = bls_batch.verify_many(items)
    else:
        got = [batch_verify_aggregates([it]) for it in items]
    assert got == want
    pairings = kernel_counters()["bls.pairings"]
    # an item that does not parse is refused before any pairing
    assert pairings >= 1 and samples() - before == pairings
    if entry == "batch_verify_aggregates":
        assert pairings == sum(
            1 for pks, _, sig in items if len(pks) and bytes(sig)[0] != 1
        )
