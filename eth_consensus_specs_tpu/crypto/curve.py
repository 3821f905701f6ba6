"""BLS12-381 curve groups.

E1: y^2 = x^3 + 4        over Fq   (G1; 48-byte compressed points)
E2: y^2 = x^3 + 4(1+u)   over Fq2  (G2; 96-byte compressed points, M-twist)

Points are immutable affine pairs (None = infinity); scalar multiplication
runs in Jacobian coordinates internally. The point API is generic over the
coordinate field, so one implementation serves both groups (and the Fq12
untwisted image used by the pairing). Serialization follows the standard
compressed encoding the reference's backends emit (flag bits: compressed,
infinity, lexicographically-largest y), which is what SSZ BLSPubkey/
BLSSignature bytes contain.
"""

from __future__ import annotations

from .fields import Fq, Fq2, P, R

B1 = Fq(4)
B2 = Fq2.from_ints(4, 4)

# Public generator coordinates (standard BLS12-381 parameters)
G1_GEN = (
    Fq(0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB),
    Fq(0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1),
)
G2_GEN = (
    Fq2(
        Fq(0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8),
        Fq(0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E),
    ),
    Fq2(
        Fq(0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801),
        Fq(0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE),
    ),
)


class Point:
    """Affine point on y^2 = x^3 + b over a tower field; None coords = O."""

    __slots__ = ("x", "y", "b")

    def __init__(self, x, y, b):
        self.x, self.y, self.b = x, y, b

    @staticmethod
    def infinity(b):
        return Point(None, None, b)

    def is_infinity(self) -> bool:
        return self.x is None

    def is_on_curve(self) -> bool:
        if self.is_infinity():
            return True
        return self.y.square() == self.x.square() * self.x + self.b

    def __eq__(self, o):
        if not isinstance(o, Point):
            return NotImplemented
        if self.is_infinity() or o.is_infinity():
            return self.is_infinity() and o.is_infinity()
        return self.x == o.x and self.y == o.y

    def __hash__(self):
        return hash((None, None) if self.is_infinity() else (self.x, self.y))

    def __neg__(self):
        if self.is_infinity():
            return self
        return Point(self.x, -self.y, self.b)

    def __add__(self, o: "Point") -> "Point":
        if self.is_infinity():
            return o
        if o.is_infinity():
            return self
        if self.x == o.x:
            if self.y == o.y:
                return self.double()
            return Point.infinity(self.b)
        lam = (o.y - self.y) * (o.x - self.x).inv()
        x3 = lam.square() - self.x - o.x
        y3 = lam * (self.x - x3) - self.y
        return Point(x3, y3, self.b)

    def double(self) -> "Point":
        if self.is_infinity() or self.y.is_zero():
            return Point.infinity(self.b)
        x_sq = self.x.square()
        lam = (x_sq + x_sq + x_sq) * (self.y + self.y).inv()
        x3 = lam.square() - self.x - self.x
        y3 = lam * (self.x - x3) - self.y
        return Point(x3, y3, self.b)

    def __sub__(self, o):
        return self + (-o)

    def mul(self, k: int) -> "Point":
        """Scalar multiplication (Jacobian double-and-add internally;
        routed through the native core for the two curve groups)."""
        k = int(k)
        if k < 0:
            return (-self).mul(-k)
        if k == 0 or self.is_infinity():
            return Point.infinity(self.b)
        from eth_consensus_specs_tpu.crypto import native_bridge as nb

        if nb.enabled():
            if isinstance(self.x, Fq):
                r = nb.g1_mul((self.x.n, self.y.n), k)
                return (
                    Point.infinity(self.b)
                    if r is None
                    else Point(Fq(r[0]), Fq(r[1]), self.b)
                )
            if isinstance(self.x, Fq2):
                r = nb.g2_mul(((self.x.c0.n, self.x.c1.n), (self.y.c0.n, self.y.c1.n)), k)
                if r is None:
                    return Point.infinity(self.b)
                (x0, x1), (y0, y1) = r
                return Point(Fq2(Fq(x0), Fq(x1)), Fq2(Fq(y0), Fq(y1)), self.b)
        jx, jy, jz = _to_jacobian(self)
        rx, ry, rz = None, None, None  # infinity
        while k:
            if k & 1:
                if rx is None:
                    rx, ry, rz = jx, jy, jz
                else:
                    rx, ry, rz = _jac_add(rx, ry, rz, jx, jy, jz)
            jx, jy, jz = _jac_double(jx, jy, jz)
            k >>= 1
        if rx is None:
            return Point.infinity(self.b)
        return _from_jacobian(rx, ry, rz, self.b)

    def __repr__(self):
        if self.is_infinity():
            return "Point(infinity)"
        return f"Point({self.x!r}, {self.y!r})"


def _to_jacobian(p: Point):
    return p.x, p.y, type(p.x).one()


def _jac_double(X, Y, Z):
    if Y.is_zero():
        return None, None, None
    A = X.square()
    B = Y.square()
    C = B.square()
    t = X + B
    D = (t.square() - A - C)
    D = D + D
    E = A + A + A
    F = E.square()
    X3 = F - D - D
    eight_c = C + C
    eight_c = eight_c + eight_c
    eight_c = eight_c + eight_c
    Y3 = E * (D - X3) - eight_c
    Z3 = (Y * Z)
    Z3 = Z3 + Z3
    return X3, Y3, Z3


def _jac_add(X1, Y1, Z1, X2, Y2, Z2):
    if Z1 is None:
        return X2, Y2, Z2
    if Z2 is None:
        return X1, Y1, Z1
    Z1Z1 = Z1.square()
    Z2Z2 = Z2.square()
    U1 = X1 * Z2Z2
    U2 = X2 * Z1Z1
    S1 = Y1 * Z2 * Z2Z2
    S2 = Y2 * Z1 * Z1Z1
    if U1 == U2:
        if S1 == S2:
            return _jac_double(X1, Y1, Z1)
        return None, None, None  # P + (-P) = O
    H = U2 - U1
    I = (H + H).square()
    J = H * I
    rr = S2 - S1
    rr = rr + rr
    V = U1 * I
    X3 = rr.square() - J - V - V
    Y3 = rr * (V - X3) - (S1 * J + S1 * J)
    Z3 = ((Z1 + Z2).square() - Z1Z1 - Z2Z2) * H
    return X3, Y3, Z3


def _from_jacobian(X, Y, Z, b) -> Point:
    if Z is None or Z.is_zero():
        return Point.infinity(b)
    zinv = Z.inv()
    z2 = zinv.square()
    return Point(X * z2, Y * z2 * zinv, b)


# --- group constructors ----------------------------------------------------


def g1_generator() -> Point:
    return Point(G1_GEN[0], G1_GEN[1], B1)


def g2_generator() -> Point:
    return Point(G2_GEN[0], G2_GEN[1], B2)


def g1_infinity() -> Point:
    return Point.infinity(B1)


def g2_infinity() -> Point:
    return Point.infinity(B2)


def in_subgroup(p: Point) -> bool:
    """Order check: r*P == O (exact; native-accelerated for G1/G2)."""
    if p.is_infinity():
        return True
    from eth_consensus_specs_tpu.crypto import native_bridge as nb

    if nb.enabled():
        if isinstance(p.x, Fq):
            return nb.g1_in_subgroup((p.x.n, p.y.n))
        if isinstance(p.x, Fq2):
            return nb.g2_in_subgroup(((p.x.c0.n, p.x.c1.n), (p.y.c0.n, p.y.c1.n)))
    return p.mul(R).is_infinity()


# --- compressed serialization ---------------------------------------------
# Flag bits on the first byte: 0x80 compressed, 0x40 infinity, 0x20 largest-y.


def g1_to_bytes(p: Point) -> bytes:
    if p.is_infinity():
        return bytes([0xC0]) + b"\x00" * 47
    data = bytearray(p.x.n.to_bytes(48, "big"))
    data[0] |= 0x80
    if p.y.sign():
        data[0] |= 0x20
    return bytes(data)


def g1_from_bytes(data: bytes, subgroup_check: bool = True) -> Point:
    if len(data) != 48:
        raise ValueError("G1 compressed point must be 48 bytes")
    flags = data[0]
    if not flags & 0x80:
        raise ValueError("uncompressed G1 encoding not supported")
    if flags & 0x40:
        if any(data[1:]) or flags & 0x3F:
            raise ValueError("malformed G1 infinity encoding")
        return g1_infinity()
    xn = int.from_bytes(bytes([flags & 0x1F]) + data[1:], "big")
    if xn >= P:
        raise ValueError("G1 x coordinate out of range")
    x = Fq(xn)
    y2 = x.square() * x + B1
    y = y2.sqrt()
    if y is None:
        raise ValueError("G1 x coordinate not on curve")
    if y.sign() != (1 if flags & 0x20 else 0):
        y = -y
    p = Point(x, y, B1)
    if subgroup_check and not in_subgroup(p):
        raise ValueError("G1 point not in the prime-order subgroup")
    return p


def g2_to_bytes(p: Point) -> bytes:
    if p.is_infinity():
        return bytes([0xC0]) + b"\x00" * 95
    data = bytearray(p.x.c1.n.to_bytes(48, "big") + p.x.c0.n.to_bytes(48, "big"))
    data[0] |= 0x80
    if p.y.sign():
        data[0] |= 0x20
    return bytes(data)


def g2_from_bytes(data: bytes, subgroup_check: bool = True) -> Point:
    if len(data) != 96:
        raise ValueError("G2 compressed point must be 96 bytes")
    if subgroup_check:
        # one native call for the common (checked) path: parse + sqrt +
        # sign + psi subgroup check; ValueError semantics preserved. The
        # pure path below stays the oracle — every accept/reject class is
        # cross-checked in tests/test_native_g2_decompress.py.
        from eth_consensus_specs_tpu.crypto import native_bridge as nb

        if nb.enabled():
            raw = nb.g2_decompress(bytes(data))
            if raw is None:
                return g2_infinity()
            (x0, x1), (y0, y1) = raw
            return Point(Fq2(Fq(x0), Fq(x1)), Fq2(Fq(y0), Fq(y1)), B2)
    flags = data[0]
    if not flags & 0x80:
        raise ValueError("uncompressed G2 encoding not supported")
    if flags & 0x40:
        if any(data[1:]) or flags & 0x3F:
            raise ValueError("malformed G2 infinity encoding")
        return g2_infinity()
    x1 = int.from_bytes(bytes([flags & 0x1F]) + data[1:48], "big")
    x0 = int.from_bytes(data[48:], "big")
    if x0 >= P or x1 >= P:
        raise ValueError("G2 x coordinate out of range")
    x = Fq2(Fq(x0), Fq(x1))
    y2 = x.square() * x + B2
    y = y2.sqrt()
    if y is None:
        raise ValueError("G2 x coordinate not on curve")
    if y.sign() != (1 if flags & 0x20 else 0):
        y = -y
    p = Point(x, y, B2)
    if subgroup_check and not in_subgroup(p):
        raise ValueError("G2 point not in the prime-order subgroup")
    return p


def g2_from_bytes_many(encodings: list[bytes]) -> list[Point | None]:
    """:func:`g2_from_bytes` of each encoding, None where it raises: the
    native core decides the 96-byte ones in ONE call, shared out over the
    host's cores."""
    from eth_consensus_specs_tpu.crypto import native_bridge as nb

    out: list[Point | None] = [None] * len(encodings)
    if not nb.enabled():
        for k, data in enumerate(encodings):
            try:
                out[k] = g2_from_bytes(data)
            except ValueError:
                pass
        return out
    sized = [k for k, data in enumerate(encodings) if len(data) == 96]
    points, status = nb.g2_decompress_many(b"".join(encodings[k] for k in sized))
    for k, raw, flag in zip(sized, points, status):
        if flag == 2:
            out[k] = g2_infinity()
        elif flag == 1:
            (x0, x1), (y0, y1) = raw
            out[k] = Point(Fq2(Fq(x0), Fq(x1)), Fq2(Fq(y0), Fq(y1)), B2)
    return out
