"""The C core's per-item legs shared out over the host's cores
(crypto/native_bridge `_slices`, `_over_threads`): every sliced leg gives
what one call gives, item for item and bit for bit, whatever the slices;
a check of two pairs stays one call on the calling thread."""

from __future__ import annotations

import hashlib
import random

import numpy as np
import pytest

from eth_consensus_specs_tpu.crypto import native_bridge as nb
from eth_consensus_specs_tpu.crypto import signature
from eth_consensus_specs_tpu.crypto.curve import (
    B2,
    Point,
    g1_generator,
    g2_from_bytes,
    g2_from_bytes_many,
    g2_generator,
    g2_infinity,
    g2_to_bytes,
    in_subgroup,
)
from eth_consensus_specs_tpu.crypto.fields import P, R, Fq, Fq2
from eth_consensus_specs_tpu.crypto.hash_to_curve import (
    hash_to_field_fq2,
    hash_to_g2,
    hash_to_g2_many,
)
from eth_consensus_specs_tpu.crypto.pairing import _g1_raw, _g2_raw, pairing_check

pytestmark = pytest.mark.skipif(not nb.enabled(), reason="native core unavailable")


def cpus(monkeypatch, n: int) -> None:
    """The CPUs the slicing sees, held at `n` for the test."""
    monkeypatch.setattr(nb, "_cpus", lambda: n)


def even_parts(n: int, k: int) -> list[tuple[int, int]]:
    """`n` items cut into exactly `k` slices of near-equal size."""
    bounds = [n * j // k for j in range(k + 1)]
    return [(lo, hi - lo) for lo, hi in zip(bounds, bounds[1:])]


# ------------------------------------------------------------ the pairing --

N_PAIRS = 9


def valid_pairs() -> list:
    """N_PAIRS pairs whose pairings multiply to 1, one of them with its G1
    point at infinity (a slice of it alone is a Miller value of 1)."""
    rng = random.Random(38)
    g1, g2 = g1_generator(), g2_generator()
    pairs, total = [], 0
    for i in range(N_PAIRS - 1):
        a, b = rng.randrange(1, R), rng.randrange(1, R)
        if i == 3:
            pairs.append((None, _g2_raw(g2.mul(b))))
            continue
        pairs.append((_g1_raw(g1.mul(a)), _g2_raw(g2.mul(b))))
        total += a * b
    pairs.append((_g1_raw(-g1.mul(total % R)), _g2_raw(g2)))
    return pairs


def wrong_at(pairs: list, i: int) -> list:
    """The pairs with pair `i` made wrong: its G1 point moved by the
    generator, so the product is no longer 1."""
    g1 = g1_generator()
    p, q = pairs[i]
    moved = g1 if p is None else Point(Fq(p[0]), Fq(p[1]), g1.b) + g1
    return pairs[:i] + [(_g1_raw(moved), q)] + pairs[i + 1 :]


@pytest.mark.parametrize("k", range(1, N_PAIRS + 1))
def test_the_sliced_pairing_check_equals_the_one_call_check(k):
    lib = nb.get_bls_lib()
    pairs = valid_pairs()
    parts = even_parts(N_PAIRS, k)

    def both(ps):
        src = nb._pairing_src(ps)
        one = bool(lib.bls_pairing_check(len(ps), *src))
        return nb._pairing_check(src, parts), one

    assert both(pairs) == (True, True)
    for at, count in parts:  # one wrong pair in each slice in turn
        assert both(wrong_at(pairs, at + count // 2)) == (False, False), (k, at)


def test_a_slice_that_fails_fails_the_check_closed(monkeypatch):
    lib = nb.get_bls_lib()
    src = nb._pairing_src(valid_pairs())
    real = lib.bls_pairing_miller

    class Lib:
        def __getattr__(self, name):
            return getattr(lib, name)

        @staticmethod
        def bls_pairing_miller(n, g1, g2, flags, out):
            return 0 if n and g1 == nb.ctypes.addressof(src[0]) else real(n, g1, g2, flags, out)

    monkeypatch.setattr(nb, "get_bls_lib", Lib)
    assert not nb._pairing_check(src, even_parts(N_PAIRS, 3))
    assert nb._pairing_check(src, even_parts(N_PAIRS, 1))


def test_native_slices_pairing_reads_one_for_two_pairs_and_more_for_129(
    monkeypatch, kernel_counters
):
    cpus(monkeypatch, 13)
    g1, g2 = g1_generator(), g2_generator()
    p, q = g1.mul(5), g2.mul(7)
    assert pairing_check([(p, q), (-p, q)])
    assert kernel_counters().get("native.slices.pairing") == 1
    rng = random.Random(129)
    scalars = [(rng.randrange(1, R), rng.randrange(1, R)) for _ in range(128)]
    pairs = [(g1.mul(a), g2.mul(b)) for a, b in scalars]
    pairs.append((-g1.mul(sum(a * b for a, b in scalars) % R), g2))
    assert pairing_check(pairs)
    assert kernel_counters().get("native.slices.pairing") == 1 + 13
    assert not pairing_check(pairs[:-1] + [(g1, g2)])


# ------------------------------------------------- signature decompression --


def off_the_curve() -> bytes:
    x0 = 1
    while True:
        x = Fq2(Fq(x0), Fq(5))
        if (x.square() * x + B2).sqrt() is None:
            data = bytearray(x.c1.n.to_bytes(48, "big") + x.c0.n.to_bytes(48, "big"))
            data[0] |= 0x80
            return bytes(data)
        x0 += 1


def outside_the_subgroup() -> bytes:
    x0 = 1
    while True:
        x = Fq2(Fq(x0), Fq(3))
        y = (x.square() * x + B2).sqrt()
        if y is not None and not in_subgroup(Point(x, y, B2)):
            return g2_to_bytes(Point(x, y, B2))
        x0 += 1


def encodings(seed: int) -> list[bytes]:
    """Valid points of both signs, the point at infinity, and every kind of
    refusal, at positions drawn from `seed`."""
    rng = random.Random(seed)
    g2 = g2_generator()
    valid = [g2_to_bytes(g2.mul(rng.randrange(1, R))) for _ in range(40)]
    infinity = g2_to_bytes(g2_infinity())
    flag_clear = bytes([valid[0][0] & 0x7F]) + valid[0][1:]
    bad_infinity = infinity[:50] + b"\x01" + infinity[51:]
    signed_infinity = bytes([infinity[0] | 0x20]) + infinity[1:]
    x_at_p = bytes([P.to_bytes(48, "big")[0] | 0x80]) + P.to_bytes(48, "big")[1:] + bytes(48)
    out = valid + [infinity] * 3 + [flag_clear, bad_infinity, signed_infinity, x_at_p,
                                    b"\xff" * 96, off_the_curve(), outside_the_subgroup()]
    rng.shuffle(out)
    return out


def one_at_a_time(data: bytes):
    try:
        point = nb.g2_decompress(data)
    except ValueError:
        return None, 0
    return point, 2 if point is None else 1


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_g2_decompress_many_equals_g2_decompress_item_for_item(monkeypatch, seed):
    cpus(monkeypatch, 4)
    data = encodings(seed)
    assert len(nb._slices("g2_decompress", len(data))) == 4
    points, status = nb.g2_decompress_many(b"".join(data))
    assert list(zip(points, status)) == [one_at_a_time(d) for d in data]
    # the curve layer's form, wrong lengths among the encodings
    data += [data[0][:95], data[0] + b"\x00"]
    want = []
    for d in data:
        try:
            want.append(g2_from_bytes(d))
        except ValueError:
            want.append(None)
    assert g2_from_bytes_many(data) == want
    with nb.disabled():
        assert g2_from_bytes_many(data) == want


# ---------------------------------------------------------- hash-to-G2 map --


def test_g2_map_from_fields_many_equals_g2_map_from_fields(monkeypatch):
    cpus(monkeypatch, 3)
    hash_to_g2(b"the parameters are sent")  # the map stage's constants in the core
    fields = [((0, 0), (0, 0)), ((0, 0), (5, 7)), ((5, 7), (5, 7))]
    for i in range(21):
        u0, u1 = hash_to_field_fq2(i.to_bytes(4, "big"), 2)
        fields.append(((u0.c0.n, u0.c1.n), (u1.c0.n, u1.c1.n)))
    assert len(nb._slices("g2_map", len(fields))) == 3
    assert nb.g2_map_from_fields_many(fields) == [nb.g2_map_from_fields(*f) for f in fields]


def test_hash_to_g2_many_equals_hash_to_g2_and_the_oracle(monkeypatch):
    cpus(monkeypatch, 4)
    msgs = [hashlib.sha256(bytes([i])).digest() for i in range(16)]
    got = hash_to_g2_many(msgs)
    assert got == [hash_to_g2(m) for m in msgs]
    with nb.disabled():
        assert hash_to_g2_many(msgs[:3]) == got[:3]


# ----------------------------------------------------------------- G2 fold --


@pytest.mark.parametrize("k", [2, 3, 4])
def test_the_sliced_g2_fold_equals_one_g2_msm(monkeypatch, k):
    rng = random.Random(k)
    g2 = g2_generator()
    points = [_g2_raw(g2.mul(rng.randrange(1, R))) for _ in range(32 * k)]
    points[5] = None  # a term at infinity
    scalars = [rng.getrandbits(64) | 1 for _ in points]
    cpus(monkeypatch, 1)
    one = nb.g2_msm(points, scalars)
    cpus(monkeypatch, k)
    assert len(nb._slices("g2_msm", len(points))) == k
    assert nb.g2_msm(points, scalars) == one
    # a slice whose terms cancel sums to the point at infinity; all of them
    cpus(monkeypatch, 2)
    live, terms = points[6:38], scalars[6:38]
    cancel = live[:16] + [(x, ((-y0) % P, (-y1) % P)) for x, (y0, y1) in live[:16]]
    assert nb.g2_msm(live + cancel, terms + terms[:16] * 2) == nb.g2_msm(live, terms)
    assert nb.g2_msm(cancel + cancel, terms[:16] * 4) is None


# ------------------------------------------------------- the served flush --

BASE = 0x51CE0000
N_KEYS = 24


@pytest.fixture(scope="module")
def registry():
    return [signature.sk_to_pk(BASE + v) for v in range(N_KEYS)]


def block_of_128(registry, wrong: tuple[int, int]) -> list:
    from eth_consensus_specs_tpu.ops.key_table import KeyTable

    rng = np.random.default_rng(128)
    items = []
    for i in range(128):
        signers = np.sort(rng.choice(N_KEYS, size=int(rng.integers(1, 6)), replace=False))
        msg = hashlib.sha256(i.to_bytes(2, "big") if i % 3 else b"shared").digest()
        sk = sum(BASE + int(v) for v in signers) % R
        sig = signature.sign(sk + 1 if i in wrong else sk, msg)
        items.append((signers, msg, sig))
    return items, KeyTable(registry)


def test_verify_many_at_128_items_with_a_wrong_aggregate_in_each_half(monkeypatch, registry):
    from eth_consensus_specs_tpu.ops import bls_batch

    wrong = (37, 101)
    items, table = block_of_128(registry, wrong)
    cpus(monkeypatch, 8)
    sliced = bls_batch.verify_many(items, keys=table)
    cpus(monkeypatch, 1)
    one_call = bls_batch.verify_many(items, keys=table)
    assert sliced == one_call == [i not in wrong for i in range(128)]
