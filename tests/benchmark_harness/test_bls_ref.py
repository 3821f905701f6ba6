"""benchmark/reference/bls_ref.py (Python integers and hashlib, nothing of
the program) against the program's own oracle: crypto/hash_to_curve for the
hash, crypto/signature for keys, signatures and FastAggregateVerify by a
real pairing."""

from __future__ import annotations

import hashlib

import pytest

from benchmark.reference import bls_ref as ref
from eth_consensus_specs_tpu.crypto import hash_to_curve, signature
from eth_consensus_specs_tpu.crypto.curve import g2_to_bytes

MESSAGE = hashlib.sha256(b"a committee's attestation data").digest()


@pytest.fixture(scope="module")
def known():
    return ref.KnownKeys(2147483777, 40)


def test_the_reference_imports_nothing_of_the_program():
    source = open(ref.__file__).read()
    assert "eth_consensus_specs_tpu" not in source and "import jax" not in source


@pytest.mark.parametrize("tag", [0, 1, 2, 3])
def test_hash_to_g2_equals_the_programs(tag):
    message = hashlib.sha256(bytes([tag])).digest() * (tag + 1)
    assert ref.g2_compress(ref.hash_to_g2(message)) == g2_to_bytes(hash_to_curve.hash_to_g2(message))


def test_fp2_square_roots_and_both_compressions_round_trip():
    for n in range(1, 6):
        a = (n * 7919 % ref.P, n * 104729 % ref.P)
        assert ref.f2_sqr(ref.f2_sqrt(ref.f2_sqr(a))) == ref.f2_sqr(a)
    point = ref.g2_mul(ref.hash_to_g2(MESSAGE), 12345)
    assert ref.g2_equal(ref.g2_decompress(ref.g2_compress(point)), point)
    assert ref.g2_compress(ref.INF2) == bytes([0xC0]) + bytes(95)
    key = ref.sk_to_pk(77)
    assert ref.g1_compress(ref.g1_decompress(key)) == key


@pytest.mark.parametrize("sk", [1, 98765, ref.R - 2])
def test_keys_and_signatures_equal_the_programs(sk):
    assert ref.sk_to_pk(sk) == signature.sk_to_pk(sk)
    assert ref.sign(sk, MESSAGE) == signature.sign(sk, MESSAGE)


def test_the_recipes_keys_are_sk_to_pk_across_a_stride(known):
    points = ref.consecutive_points(ref.g1_mul(ref.G1, known.base), 21, stride=8)
    assert [ref.g1_compress(p) for p in points] == known.pubkeys[:21]
    for v in (0, 7, 8, 9, 39):
        assert known.pubkeys[v] == signature.sk_to_pk(known.base + v)


def _aggregate(known, signers, message=MESSAGE):
    return [known.pubkeys[v] for v in signers], ref.sign(known.secret_sum(signers), message)


@pytest.mark.parametrize("case", ["valid", "valid_one_signer", "another_message_signed",
                                  "a_signer_left_out", "a_signer_too_many"])
def test_fast_aggregate_verify_equals_the_pairing(known, case):
    signers = [3, 11, 12, 30, 39]
    pubkeys, sig = _aggregate(known, signers)
    if case == "valid_one_signer":
        pubkeys, sig = _aggregate(known, [5])
    elif case == "another_message_signed":
        sig = _aggregate(known, signers, b"\x01" * 32)[1]
    elif case == "a_signer_left_out":
        pubkeys = pubkeys[:-1]
    elif case == "a_signer_too_many":
        pubkeys = pubkeys + [known.pubkeys[0]]
    want = signature.fast_aggregate_verify(pubkeys, MESSAGE, sig)
    assert want == case.startswith("valid")
    assert ref.fast_aggregate_verify(known, pubkeys, MESSAGE, sig) == want
    # the control accepts whatever is well formed
    assert ref.accept_well_formed(known, pubkeys, MESSAGE, sig)


def test_malformed_inputs_are_refused_and_foreign_keys_are_an_error(known):
    pubkeys, sig = _aggregate(known, [1, 2])
    assert not ref.fast_aggregate_verify(known, [], MESSAGE, sig)
    assert not ref.fast_aggregate_verify(known, pubkeys, MESSAGE, b"\x00" * 96)
    assert not ref.accept_well_formed(known, pubkeys, MESSAGE, sig[:-1] + bytes([sig[-1] ^ 1]))
    with pytest.raises(ValueError):  # a point of the curve outside the subgroup
        x = next(x for x in range(1, 50)
                 if ref.fp_sqrt((x ** 3 + 4) % ref.P) and not _in_g1(x))
        ref.g1_decompress(bytes([0x80]) + x.to_bytes(48, "big")[1:])
    with pytest.raises(KeyError):  # the generator's error, not a verdict
        ref.fast_aggregate_verify(known, [ref.sk_to_pk(5)], MESSAGE, sig)


def _in_g1(x: int) -> bool:
    y = ref.fp_sqrt((x ** 3 + 4) % ref.P)
    return ref.g1_mul((x, y), ref.R - 1) == (x, ref.P - y)
