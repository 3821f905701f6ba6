"""Plain reference for Deneb blob KZG proofs: field and G1 arithmetic in
Python integers, hashlib, nothing of the program.

The service under test runs on the repo's INSECURE testing setup, whose
trapdoor tau is public by construction (tau = sha256(tag) mod r). With tau
in hand the pairing check e(C - y G, G2) == e(Q, (tau - z) G2) is the G1
equation C - y G == (tau - z) Q, since G1 has prime order: the same verdict
for every well-formed (blob, commitment, proof), with no pairing. The same
trapdoor makes commitments and proofs from a blob with two scalar
multiplications, which is what the traffic generator uses.
"""

from __future__ import annotations

import hashlib

# BLS12-381: base field, subgroup order, generator of G1 (y^2 = x^3 + 4)
P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
G1 = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
FIELD_ELEMENTS_PER_BLOB = 4096
BYTES_PER_BLOB = 32 * FIELD_ELEMENTS_PER_BLOB
FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"
# crypto/kzg_setup.py SETUP_TAG: the tag the repo's testing setup is made from
SETUP_TAG = b"eth-consensus-specs-tpu insecure kzg testing setup v1"
TAU = int.from_bytes(hashlib.sha256(SETUP_TAG).digest(), "big") % R


# ------------------------------------------------------------------- G1 --
# Jacobian (X, Y, Z); None is the point at infinity.


def _double(pt):
    if pt is None:
        return None
    x, y, z = pt
    a = x * x % P
    b = y * y % P
    c = b * b % P
    d = 2 * ((x + b) * (x + b) - a - c) % P
    e = 3 * a % P
    x3 = (e * e - 2 * d) % P
    return x3, (e * (d - x3) - 8 * c) % P, 2 * y * z % P


def _add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1, z1 = p1
    x2, y2, z2 = p2
    z1z1, z2z2 = z1 * z1 % P, z2 * z2 % P
    u1, u2 = x1 * z2z2 % P, x2 * z1z1 % P
    s1, s2 = y1 * z2 * z2z2 % P, y2 * z1 * z1z1 % P
    if u1 == u2:
        return _double(p1) if s1 == s2 else None
    h, r = (u2 - u1) % P, (s2 - s1) % P
    hh = h * h % P
    hhh, v = h * hh % P, u1 * hh % P
    x3 = (r * r - hhh - 2 * v) % P
    return x3, (r * (v - x3) - s1 * hhh) % P, z1 * z2 * h % P


def g1_mul(pt, k: int):
    acc = None
    for bit in bin(k % R)[2:]:
        acc = _double(acc)
        if bit == "1":
            acc = _add(acc, pt)
    return acc


def _neg(pt):
    return None if pt is None else (pt[0], -pt[1] % P, pt[2])


def g1_equal(p1, p2) -> bool:
    if p1 is None or p2 is None:
        return p1 is None and p2 is None
    x1, y1, z1 = p1
    x2, y2, z2 = p2
    z1z1, z2z2 = z1 * z1 % P, z2 * z2 % P
    return (x1 * z2z2 - x2 * z1z1) % P == 0 and (y1 * z2 * z2z2 - y2 * z1 * z1z1) % P == 0


def g1_compress(pt) -> bytes:
    """48 bytes, big endian x with the three flag bits of the ZCash format."""
    if pt is None:
        return b"\xc0" + bytes(47)
    x, y, z = pt
    zi = pow(z, P - 2, P)
    x, y = x * zi * zi % P, y * zi * zi * zi % P
    flags = 0x80 | (0x20 if y > (P - 1) // 2 else 0)
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= flags
    return bytes(out)


def g1_decompress(data: bytes):
    """The point, or ValueError for bytes that name none. No subgroup check:
    the traffic only sends points made by g1_mul."""
    if len(data) != 48 or not data[0] & 0x80:
        raise ValueError("not a compressed G1 point")
    if data[0] & 0x40:
        if any(data[1:]) or data[0] & 0x3F:
            raise ValueError("malformed infinity")
        return None
    x = int.from_bytes(data, "big") & ((1 << 381) - 1)
    if x >= P:
        raise ValueError("x out of range")
    y2 = (x * x * x + 4) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        raise ValueError("x is on no point")
    if (y > (P - 1) // 2) != bool(data[0] & 0x20):
        y = P - y
    return x, y, 1


G1_JAC = (G1[0], G1[1], 1)


# ------------------------------------------------------- the blob's field --


def _roots_brp() -> list[int]:
    n = FIELD_ELEMENTS_PER_BLOB
    root = pow(7, (R - 1) // n, R)
    powers, acc = [], 1
    for _ in range(n):
        powers.append(acc)
        acc = acc * root % R
    bits = n.bit_length() - 1
    return [powers[int(format(i, f"0{bits}b")[::-1], 2)] for i in range(n)]


ROOTS_BRP = _roots_brp()
_ROOT_INDEX = {w: i for i, w in enumerate(ROOTS_BRP)}


def blob_to_polynomial(blob: bytes) -> list[int]:
    if len(blob) != BYTES_PER_BLOB:
        raise ValueError("blob of the wrong length")
    poly = [int.from_bytes(blob[i : i + 32], "big") for i in range(0, BYTES_PER_BLOB, 32)]
    if max(poly) >= R:
        raise ValueError("field element out of range")
    return poly


def challenge(blob: bytes, commitment: bytes) -> int:
    data = (
        FIAT_SHAMIR_PROTOCOL_DOMAIN
        + FIELD_ELEMENTS_PER_BLOB.to_bytes(16, "big")
        + blob
        + commitment
    )
    return int.from_bytes(hashlib.sha256(data).digest(), "big") % R


def evaluate(poly: list[int], z: int) -> int:
    """Barycentric evaluation of the blob's polynomial (values at the
    bit-reversed roots of unity) at z."""
    if z in _ROOT_INDEX:
        return poly[_ROOT_INDEX[z]]
    n = FIELD_ELEMENTS_PER_BLOB
    denominators = [(z - w) % R for w in ROOTS_BRP]
    prefix, acc = [], 1
    for d in denominators:
        prefix.append(acc)
        acc = acc * d % R
    inv = pow(acc, R - 2, R)
    total = 0
    for i in range(n - 1, -1, -1):
        total += poly[i] * ROOTS_BRP[i] % R * (prefix[i] * inv % R)
        inv = inv * denominators[i] % R
    return total % R * ((pow(z, n, R) - 1) % R) % R * pow(n, R - 2, R) % R


# ------------------------------------------------------------ the verdict --


def commit_and_prove(blob: bytes) -> tuple[bytes, bytes]:
    """(commitment, proof) of a blob under the testing setup, by the
    trapdoor: C = p(tau) G, Q = (p(tau) - p(z)) / (tau - z) G."""
    poly = blob_to_polynomial(blob)
    p_tau = evaluate(poly, TAU)
    commitment = g1_compress(g1_mul(G1_JAC, p_tau))
    z = challenge(blob, commitment)
    q_tau = (p_tau - evaluate(poly, z)) * pow((TAU - z) % R, R - 2, R) % R
    return commitment, g1_compress(g1_mul(G1_JAC, q_tau))


def random_sidecar(rng) -> tuple[bytes, bytes, bytes]:
    """(blob, commitment, proof) from a numpy generator: 31 random bytes a
    field element, so below the modulus by width."""
    import numpy as np

    raw = rng.integers(0, 256, (FIELD_ELEMENTS_PER_BLOB, 32), dtype=np.uint8)
    raw[:, 0] = 0
    blob = raw.tobytes()
    return (blob, *commit_and_prove(blob))


def verify_blob(blob: bytes, commitment: bytes, proof: bytes) -> bool:
    """verify_blob_kzg_proof for one sidecar; malformed input is False."""
    try:
        poly = blob_to_polynomial(blob)
        c_pt, q_pt = g1_decompress(commitment), g1_decompress(proof)
    except ValueError:
        return False
    z = challenge(blob, commitment)
    y = evaluate(poly, z)
    lhs = _add(c_pt, _neg(g1_mul(G1_JAC, y)))
    return g1_equal(lhs, g1_mul(q_pt, (TAU - z) % R))


def accept_without_proof(blob: bytes, commitment: bytes, proof: bytes) -> bool:
    """The control: the sidecar is parsed and its commitment is checked to be
    a point, and the proof is never opened. It breaks the guarantee that an
    accepted sidecar's proof opens the commitment at the challenge."""
    try:
        blob_to_polynomial(blob)
        g1_decompress(commitment)
        g1_decompress(proof)
    except ValueError:
        return False
    return True
