"""BLS12-381 signatures as the consensus spec uses them (public keys in G1,
signatures in G2, proof-of-possession ciphersuite), in Python integers and
hashlib: nothing of the program is imported here. It is the benchmark's
generator (keys, signatures) and the reference its verdicts are compared
with.

Fp is an integer mod P; Fp2 = Fp[u]/(u^2 + 1) a pair (c0, c1); a G1 point
an affine pair or None; a G2 point Jacobian (X, Y, Z) over Fp2 with Z = 0
at infinity. Compression is ZCash's (48 and 96 bytes, flags in the top
three bits). hash_to_g2 is RFC 9380's BLS12381G2_XMD:SHA-256_SSWU_RO_
(sections 5.3.1, 6.6.2, 8.8.2, appendix E.3 for the 3-isogeny, G.3 for the
cofactor). All constants are the published ones.

The reference needs no pairing, because the benchmark's secret keys are
public by recipe, sk_v = base + v (`KnownKeys`), as the blob cell's tau is:
e(sum pk_i, H(m)) == e(G1, sig) is, G2 having prime order, the G2 equation
sig == (sum sk_i) * H(m). A key the recipe did not make is the generator's
error, not a verdict.
"""

from __future__ import annotations

import functools
import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
BLS_X = -0xD201000000010000
HALF_P = (P - 1) // 2
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

G1 = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)

# ------------------------------------------------------------------- Fp2 --

ZERO2, ONE2 = (0, 0), (1, 0)


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    return ((a[0] * b[0] - a[1] * b[1]) % P, (a[0] * b[1] + a[1] * b[0]) % P)


def f2_sqr(a):
    return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)


def f2_scale(a, k: int):
    return (a[0] * k % P, a[1] * k % P)


def f2_conj(a):
    return (a[0], -a[1] % P)


def f2_inv(a):
    n = pow(a[0] * a[0] + a[1] * a[1], -1, P)
    return (a[0] * n % P, -a[1] * n % P)


def f2_pow(a, e: int):
    out = ONE2
    for bit in bin(e)[2:]:
        out = f2_sqr(out)
        if bit == "1":
            out = f2_mul(out, a)
    return out


def fp_sqrt(a: int):
    s = pow(a, (P + 1) // 4, P)
    return s if s * s % P == a % P else None


def f2_sqrt(a):
    """A square root of a in Fp2, or None: from the norm, x0^2 = (a0 +- s)/2
    with s^2 = a0^2 + a1^2, then x1 = a1 / (2 x0)."""
    if a[1] == 0:
        s = fp_sqrt(a[0])
        if s is not None:
            return (s, 0)
        s = fp_sqrt(-a[0] % P)
        return None if s is None else (0, s)
    s = fp_sqrt((a[0] * a[0] + a[1] * a[1]) % P)
    if s is None:
        return None
    half = pow(2, -1, P)
    x0 = fp_sqrt((a[0] + s) * half % P)
    if x0 is None:
        x0 = fp_sqrt((a[0] - s) * half % P)
    if x0 is None or x0 == 0:
        return None
    x = (x0, a[1] * pow(2 * x0, -1, P) % P)
    return x if f2_sqr(x) == (a[0] % P, a[1] % P) else None


def f2_sgn0(a) -> int:
    return (a[0] & 1) | (int(a[0] == 0) & (a[1] & 1))


def f2_largest(y) -> bool:
    """ZCash's sign bit: y is the lexicographically larger of y and -y."""
    return y[1] > HALF_P or (y[1] == 0 and y[0] > HALF_P)


# -------------------------------------------------------------------- G1 --


def g1_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    if p[0] == q[0]:
        if (p[1] + q[1]) % P == 0:
            return None
        lam = 3 * p[0] * p[0] * pow(2 * p[1], -1, P) % P
    else:
        lam = (q[1] - p[1]) * pow(q[0] - p[0], -1, P) % P
    x = (lam * lam - p[0] - q[0]) % P
    return (x, (lam * (p[0] - x) - p[1]) % P)


def g1_mul(p, k: int):
    out = None
    for bit in bin(k % R)[2:]:
        out = g1_add(out, out)
        if bit == "1":
            out = g1_add(out, p)
    return out


def g1_compress(p) -> bytes:
    if p is None:
        return bytes([0xC0]) + bytes(47)
    raw = bytearray(p[0].to_bytes(48, "big"))
    raw[0] |= 0x80 | (0x20 if p[1] > HALF_P else 0)
    return bytes(raw)


def g1_decompress(data: bytes):
    """The affine point, None for infinity; ValueError when malformed, off
    the curve or outside the subgroup."""
    if len(data) != 48 or not data[0] & 0x80:
        raise ValueError("not a compressed G1 point")
    if data[0] & 0x40:
        if data[0] & 0x3F or any(data[1:]):
            raise ValueError("malformed infinity")
        return None
    x = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:], "big")
    y = fp_sqrt((x * x * x + 4) % P) if x < P else None
    if y is None:
        raise ValueError("not on the curve")
    if (y > HALF_P) != bool(data[0] & 0x20):
        y = P - y
    if g1_mul((x, y), R - 1) != (x, P - y):  # [r]P = O, without the last add
        raise ValueError("not in the subgroup")
    return (x, y)


def sk_to_pk(sk: int) -> bytes:
    return g1_compress(g1_mul(G1, sk))


# -------------------------------------------------------------------- G2 --

B2 = (4, 4)
INF2 = (ONE2, ONE2, ZERO2)


def g2_double(p):
    X, Y, Z = p
    A, B = f2_sqr(X), f2_sqr(Y)
    C = f2_sqr(B)
    D = f2_scale(f2_sub(f2_sub(f2_sqr(f2_add(X, B)), A), C), 2)
    E = f2_scale(A, 3)
    X3 = f2_sub(f2_sqr(E), f2_scale(D, 2))
    return (X3, f2_sub(f2_mul(E, f2_sub(D, X3)), f2_scale(C, 8)), f2_scale(f2_mul(Y, Z), 2))


def g2_add(p, q):
    if p[2] == ZERO2:
        return q
    if q[2] == ZERO2:
        return p
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1, Z2Z2 = f2_sqr(Z1), f2_sqr(Z2)
    U1, U2 = f2_mul(X1, Z2Z2), f2_mul(X2, Z1Z1)
    S1, S2 = f2_mul(f2_mul(Y1, Z2), Z2Z2), f2_mul(f2_mul(Y2, Z1), Z1Z1)
    H, r = f2_sub(U2, U1), f2_scale(f2_sub(S2, S1), 2)
    if H == ZERO2:
        return g2_double(p) if r == ZERO2 else INF2
    I = f2_sqr(f2_scale(H, 2))
    J, V = f2_mul(H, I), f2_mul(U1, I)
    X3 = f2_sub(f2_sub(f2_sqr(r), J), f2_scale(V, 2))
    Y3 = f2_sub(f2_mul(r, f2_sub(V, X3)), f2_scale(f2_mul(S1, J), 2))
    Z3 = f2_mul(f2_sub(f2_sub(f2_sqr(f2_add(Z1, Z2)), Z1Z1), Z2Z2), H)
    return (X3, Y3, Z3)


def g2_neg(p):
    return (p[0], f2_neg(p[1]), p[2])


def g2_mul(p, k: int):
    if k < 0:
        p, k = g2_neg(p), -k
    out = INF2
    for bit in bin(k)[2:]:
        out = g2_double(out)
        if bit == "1":
            out = g2_add(out, p)
    return out


def g2_affine(p):
    """(x, y) over Fp2, None at infinity."""
    if p[2] == ZERO2:
        return None
    zi = f2_inv(p[2])
    zi2 = f2_sqr(zi)
    return (f2_mul(p[0], zi2), f2_mul(p[1], f2_mul(zi2, zi)))


def g2_equal(p, q) -> bool:
    return g2_affine(p) == g2_affine(q)


def g2_compress(p) -> bytes:
    a = g2_affine(p)
    if a is None:
        return bytes([0xC0]) + bytes(95)
    (x0, x1), y = a
    raw = bytearray(x1.to_bytes(48, "big") + x0.to_bytes(48, "big"))
    raw[0] |= 0x80 | (0x20 if f2_largest(y) else 0)
    return bytes(raw)


def g2_decompress(data: bytes):
    """The Jacobian point (Z = 1), INF2 for infinity; ValueError when
    malformed, off the curve or outside the subgroup."""
    if len(data) != 96 or not data[0] & 0x80:
        raise ValueError("not a compressed G2 point")
    if data[0] & 0x40:
        if data[0] & 0x3F or any(data[1:]):
            raise ValueError("malformed infinity")
        return INF2
    x = (int.from_bytes(data[48:], "big"),
         int.from_bytes(bytes([data[0] & 0x1F]) + data[1:48], "big"))
    if x[0] >= P or x[1] >= P:
        raise ValueError("coordinate out of range")
    y = f2_sqrt(f2_add(f2_mul(f2_sqr(x), x), B2))
    if y is None:
        raise ValueError("not on the curve")
    if f2_largest(y) != bool(data[0] & 0x20):
        y = f2_neg(y)
    p = (x, y, ONE2)
    if g2_mul(p, R)[2] != ZERO2:
        raise ValueError("not in the subgroup")
    return p


# ------------------------------------------------------------ hash to G2 --

A_ISO, B_ISO, Z_SSWU = (0, 240), (1012, 1012), (P - 2, P - 1)
_K = P - 0xAAAB
ISO_X_NUM = (
    (0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,) * 2,
    (0, 0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1, 0),
)
ISO_X_DEN = ((0, _K + 0xAA63), (0xC, _K + 0xAA9F), ONE2)
ISO_Y_NUM = (
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,) * 2,
    (0, 0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10, 0),
)
ISO_Y_DEN = ((_K + 0xA8FB,) * 2, (0, _K + 0xA9D3), (0x12, _K + 0xAA99), ONE2)

# psi, the untwist-Frobenius-twist endomorphism, and its square (RFC 9380 G.3)
PSI_X = f2_inv(f2_pow((1, 1), (P - 1) // 3))
PSI_Y = f2_inv(f2_pow((1, 1), (P - 1) // 2))
PSI2_X = pow(pow(2, (P - 1) // 3, P), -1, P)


def expand_message_xmd(msg: bytes, dst: bytes, length: int) -> bytes:
    ell = -(-length // 32)
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + length.to_bytes(2, "big") + b"\x00" + dst_prime).digest()
    blocks = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(a ^ b for a, b in zip(b0, blocks[-1]))
        blocks.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(blocks)[:length]


def hash_to_field(msg: bytes, dst: bytes):
    """Two elements of Fp2 (count 2, m 2, L 64)."""
    data = expand_message_xmd(msg, dst, 256)
    e = [int.from_bytes(data[at : at + 64], "big") % P for at in range(0, 256, 64)]
    return (e[0], e[1]), (e[2], e[3])


def _iso_curve(x):
    return f2_add(f2_mul(f2_add(f2_sqr(x), A_ISO), x), B_ISO)


def map_to_curve_sswu(u):
    """Simplified SWU onto the 3-isogenous curve y^2 = x^3 + A x + B."""
    tv1 = f2_mul(Z_SSWU, f2_sqr(u))
    tv2 = f2_add(f2_sqr(tv1), tv1)
    if tv2 == ZERO2:
        x = f2_mul(B_ISO, f2_inv(f2_mul(Z_SSWU, A_ISO)))
    else:
        x = f2_mul(f2_mul(f2_neg(B_ISO), f2_inv(A_ISO)), f2_add(ONE2, f2_inv(tv2)))
    y = f2_sqrt(_iso_curve(x))
    if y is None:
        x = f2_mul(tv1, x)
        y = f2_sqrt(_iso_curve(x))
    if f2_sgn0(u) != f2_sgn0(y):
        y = f2_neg(y)
    return x, y


def _horner(coefficients, x):
    out = coefficients[-1]
    for c in reversed(coefficients[:-1]):
        out = f2_add(f2_mul(out, x), c)
    return out


def iso_map(x, y):
    """The 3-isogeny to E2, as a Jacobian point."""
    x_den, y_den = _horner(ISO_X_DEN, x), _horner(ISO_Y_DEN, x)
    if x_den == ZERO2 or y_den == ZERO2:
        return INF2
    return (f2_mul(_horner(ISO_X_NUM, x), f2_inv(x_den)),
            f2_mul(y, f2_mul(_horner(ISO_Y_NUM, x), f2_inv(y_den))), ONE2)


def psi(p):
    return (f2_mul(f2_conj(p[0]), PSI_X), f2_mul(f2_conj(p[1]), PSI_Y), f2_conj(p[2]))


def psi2(p):
    return (f2_scale(p[0], PSI2_X), f2_neg(p[1]), p[2])


def clear_cofactor(p):
    """h_eff * P by the endomorphism (RFC 9380 G.3)."""
    t1 = g2_mul(p, BLS_X)
    t2 = psi(p)
    t3 = g2_add(psi2(g2_double(p)), g2_neg(t2))
    t2 = g2_mul(g2_add(t1, t2), BLS_X)
    return g2_add(g2_add(g2_add(t3, t2), g2_neg(t1)), g2_neg(p))


@functools.lru_cache(maxsize=1 << 14)
def hash_to_g2(msg: bytes, dst: bytes = DST):
    u0, u1 = hash_to_field(msg, dst)
    return clear_cofactor(g2_add(iso_map(*map_to_curve_sswu(u0)), iso_map(*map_to_curve_sswu(u1))))


def sign(sk: int, message: bytes) -> bytes:
    """Also the aggregate of the signatures under the keys that sum to sk."""
    return g2_compress(g2_mul(hash_to_g2(message), sk % R))


# ------------------------------------------------- keys by a public recipe --


def batch_inverse(values: list[int]) -> list[int]:
    prefix, acc = [], 1
    for v in values:
        prefix.append(acc)
        acc = acc * v % P
    inv, out = pow(acc, -1, P), [0] * len(values)
    for at in range(len(values) - 1, -1, -1):
        out[at] = inv * prefix[at] % P
        inv = inv * values[at] % P
    return out


def consecutive_points(first, count: int, stride: int = 1024) -> list:
    """first, first + G1, ..., first + (count - 1) G1, affine: one point
    addition each, a stride's inversions batched into one."""
    steps = [None]  # j * G1
    for _ in range(min(stride, count) - 1):
        steps.append(g1_add(steps[-1], G1))
    stride_point = g1_add(steps[-1], G1)
    out, anchor = [], first
    while len(out) < count:
        x1, y1 = anchor
        block = steps[1 : min(stride, count - len(out))]
        out.append(anchor)
        for (x2, y2), inv in zip(block, batch_inverse([(s[0] - x1) % P for s in block])):
            lam = (y2 - y1) * inv % P  # a zero difference (anchor = j G1) would read 0 here
            x3 = (lam * lam - x1 - x2) % P
            out.append((x3, (lam * (x1 - x3) - y1) % P))
        anchor = g1_add(anchor, stride_point)
    return out


class KnownKeys:
    """The registry of a run: sk_v = base + v for v < count, base from the
    seed; the public keys compressed, and the way back from a key to v."""

    def __init__(self, seed: int, count: int):
        self.base = int.from_bytes(hashlib.sha256(b"atts sk " + str(seed).encode()).digest(), "big") >> 8
        self.pubkeys = [g1_compress(p) for p in consecutive_points(g1_mul(G1, self.base), count)]
        self.index_of = {pk: v for v, pk in enumerate(self.pubkeys)}

    def secret_sum(self, validators) -> int:
        return sum(self.base + int(v) for v in validators) % R

    def secrets_of(self, pubkeys) -> int:
        """The sum of the secret keys behind `pubkeys`; KeyError for a key
        the recipe did not make (the generator's error, not a verdict)."""
        return self.secret_sum(self.index_of[bytes(pk)] for pk in pubkeys)


@functools.lru_cache(maxsize=1 << 14)
def _signature_point(signature: bytes):
    try:
        return g2_decompress(bytes(signature))
    except ValueError:
        return None


@functools.lru_cache(maxsize=1 << 14)
def _signed_by(secret: int, message: bytes, signature: bytes) -> bool:
    sig = _signature_point(signature)
    return sig is not None and g2_equal(sig, g2_mul(hash_to_g2(message), secret))


def fast_aggregate_verify(known: KnownKeys, pubkeys, message: bytes, signature: bytes) -> bool:
    """FastAggregateVerify of the spec for keys of the recipe (all of them
    valid by construction): no key, a malformed signature or one outside
    the subgroup is refused; else sig == (sum sk_i) * H(m)."""
    if len(pubkeys) == 0:
        return False
    return _signed_by(known.secrets_of(pubkeys), bytes(message), bytes(signature))


def accept_well_formed(known: KnownKeys, pubkeys, message: bytes, signature: bytes) -> bool:
    """The control: a verifier that checks the encodings and no equation."""
    known.secrets_of(pubkeys)
    return len(pubkeys) > 0 and _signature_point(signature) is not None
