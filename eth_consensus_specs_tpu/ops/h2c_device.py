"""Batched hash-to-G2 on device (RFC 9380 BLS12381G2_XMD:SHA-256_SSWU_RO_).

The signature hot path hashes one fresh message to G2 per distinct
attestation — on the host that costs ~0.75 ms each (two Fq2 square roots
plus the cofactor ladder dominate, crypto/hash_to_curve.py).  Here the
whole field-to-curve pipeline runs as ONE jitted device program over a
fixed batch of messages:

  * hash_to_field stays host-side (SHA-256 via hashlib — cheap, and
    sha256 of short inputs is not the device's comparative advantage);
  * simplified SWU on E2' with the norm-method Fq2 square root, evaluated
    BRANCHLESSLY: both gx1/gx2 candidates, both ±sn half-branches, and
    the b==0 special case are computed for every lane and lane-selected
    to exactly the value the host oracle picks
    (crypto/fields.Fq2.sqrt + crypto/hash_to_curve.map_to_curve_sswu_g2);
  * all Fq exponentiations are packed into FOUR fixed scans (381 steps of
    square + conditional multiply each) over stacked lanes — per level
    every lane shares the same public exponent ((p+1)/4 or p-2);
  * the 3-isogeny evaluates into JACOBIAN coordinates (Z = x_den * y_den)
    so no inversion is spent before the group stage;
  * point addition of the two mapped points and the Budroni-Pintore
    cofactor ladder run in ops/g2_jacobian (bit-equal to the native C
    walk), and one final batched Fq2 inversion converts to affine.

Bit-exactness: hash_to_g2_device(msgs) == [hash_to_g2(m) for m in msgs]
exactly (tests/test_h2c_device.py), so the device path can substitute the
host/native one anywhere (reference seam: the message-side pairing input
of every verification, utils/bls.py:141-221).
"""

from __future__ import annotations

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp
from jax import lax

from eth_consensus_specs_tpu.crypto.fields import Fq, Fq2, P as P_INT
from eth_consensus_specs_tpu.crypto import hash_to_curve as h2c
from eth_consensus_specs_tpu.ops import fq12_tower as tw
from eth_consensus_specs_tpu.ops import g2_jacobian as gj
from eth_consensus_specs_tpu.ops import lazy_limbs as lz
from eth_consensus_specs_tpu.ops.lazy_limbs import LF, lf

# ---------------------------------------------------------------- consts --

_E_SQRT = np.array([int(b) for b in bin((P_INT + 1) // 4)[2:]], np.uint8)
_E_INV = np.array([int(b) for b in bin(P_INT - 2)[2:]], np.uint8)

_A_L = tw.fq2_to_limbs(h2c.A_PRIME)
_B_L = tw.fq2_to_limbs(h2c.B_PRIME)
_Z_L = tw.fq2_to_limbs(h2c.Z_SSWU)
# x1 coefficient for the regular branch: (-B) * A^-1 (a fixed Fq2 value)
_NEG_B_OVER_A_L = tw.fq2_to_limbs(-h2c.B_PRIME * h2c.A_PRIME.inv())
# x1 for the exceptional branch: B / (Z * A)
_B_OVER_ZA_L = tw.fq2_to_limbs(
    h2c.B_PRIME * (h2c.Z_SSWU * h2c.A_PRIME).inv()
)
_ONE2_L = tw.fq2_to_limbs(Fq2.one())
_INV2_L = lz.to_mont(pow(2, P_INT - 2, P_INT))
# (-1)^((p+1)/4): turns sqrt-candidate of a into the candidate of -a
_ZETA_L = lz.to_mont(pow(P_INT - 1, (P_INT + 1) // 4, P_INT))

_K_LIMBS = [
    np.stack([tw.fq2_to_limbs(c) for c in ks])
    for ks in (h2c._K1, h2c._K2, h2c._K3, h2c._K4)
]

_P_LIMBS_ARR = lz.int_to_limbs(P_INT)


# ------------------------------------------------------------- primitives --


def _pow_lanes(x: LF, bits: np.ndarray) -> LF:
    """x^e over any batch shape in ONE scan; bits MSB-first (the leading
    bit may be 0 — the accumulator starts at one)."""
    xc = lz.shrink(x)
    one = lf(jnp.broadcast_to(jnp.asarray(lz.ONE_MONT), xc.v.shape), val=P_INT - 1)

    def step(acc_v, bit):
        acc = lf(acc_v)
        sq = lz.mul(acc, acc)
        wm = lz.mul(sq, lf(xc.v))
        return jnp.where(bit != 0, wm.v, sq.v), None

    out, _ = lax.scan(step, one.v, jnp.asarray(bits))
    return lf(out)


def _canon_fq(x: LF) -> jnp.ndarray:
    """Exact canonical residue (< p) as normalized limbs — needed for
    parity (sgn0) and equality against host values."""
    s = lz.shrink(x)  # < 2p
    pv = jnp.broadcast_to(jnp.asarray(_P_LIMBS_ARR), s.v.shape)
    ge = lz._geq(s.v, pv)
    return jnp.where(ge[..., None], lz._sub_limbs(s.v, pv), s.v)


def _fq_eq(a: LF, b: LF) -> jnp.ndarray:
    return jnp.all(_canon_fq(a) == _canon_fq(b), axis=-1)


def _fq_is_zero(a: LF) -> jnp.ndarray:
    return jnp.all(_canon_fq(a) == 0, axis=-1)


def _fq2_eq(a: LF, b: LF) -> jnp.ndarray:
    return jnp.all(
        _canon_fq(LF(a.v, a.max, a.val)) == _canon_fq(LF(b.v, b.max, b.val)),
        axis=(-1, -2),
    )


def _c0(a: LF) -> LF:
    return tw._part(a, 0, 1)


def _c1(a: LF) -> LF:
    return tw._part(a, 1, 1)


def _mk2(c0: LF, c1: LF) -> LF:
    return tw._stack([c0, c1], axis=-2)


def _self_fq(mask, a: LF, b: LF) -> LF:
    return LF(
        jnp.where(mask[..., None], a.v, b.v),
        max(a.max, b.max),
        max(a.val, b.val),
    )


_ONE_PLAIN = lz.int_to_limbs(1)


def _from_mont(x: LF) -> jnp.ndarray:
    """Canonical PLAIN residue (< p): one Montgomery multiply by the
    plain constant 1 strips the 2^390 factor (mul(x, 1) = v)."""
    one = lf(jnp.broadcast_to(jnp.asarray(_ONE_PLAIN), x.v.shape), val=1)
    return _canon_fq(lz.mul(x, one))


def _sgn0(x: LF) -> jnp.ndarray:
    """RFC 9380 sgn0 for m=2 — parity is of the PLAIN value, so the
    Montgomery factor must come off first (limbs are base-2^26: bit 0 of
    limb 0 is the value's parity)."""
    c0 = _from_mont(_c0(x))
    c1 = _from_mont(_c1(x))
    sign_0 = c0[..., 0] & 1
    zero_0 = jnp.all(c0 == 0, axis=-1)
    sign_1 = c1[..., 0] & 1
    return sign_0 | (zero_0.astype(jnp.uint64) & sign_1)


# ------------------------------------------------------------ Fq2 sqrt ----
# The norm method exactly as crypto/fields.Fq2.sqrt, with every branch
# computed and lane-selected.  Returns (root, ok_mask).


def _fq2_sqrt_batch(v: LF) -> tuple[LF, jnp.ndarray]:
    a, b = _c0(v), _c1(v)
    b_zero = _fq_is_zero(b)
    v_zero = _fq_is_zero(a) & b_zero

    norm = lz.add(lz.mul(a, a), lz.mul(b, b))  # N(a+bu) = a^2 + b^2

    # L1: stacked (p+1)/4 lanes: [sn(norm), s_bz(a)]
    l1_in = tw._lane_stack([norm, a])
    l1 = _pow_lanes(l1_in, _E_SQRT)
    sn, s_bz = tw._unstack(l1, 2)

    # b==0 resolution: s_bz if s_bz^2 == a else zeta * s_bz (root of -a)
    bz_ok = _fq_eq(lz.mul(s_bz, s_bz), a)
    zeta = lf(jnp.broadcast_to(jnp.asarray(_ZETA_L), s_bz.v.shape), val=P_INT - 1)
    s_alt = lz.mul(zeta, s_bz)
    bz_root = _self_fq(bz_ok, s_bz, LF(jnp.zeros_like(s_bz.v), 0, 0))
    bz_root_c1 = _self_fq(bz_ok, LF(jnp.zeros_like(s_bz.v), 0, 0), s_alt)
    out_bz = _mk2(bz_root, bz_root_c1)

    # --- general branch ------------------------------------------------
    sn_ok = _fq_eq(lz.mul(sn, sn), norm)
    inv2 = lf(jnp.broadcast_to(jnp.asarray(_INV2_L), a.v.shape), val=P_INT - 1)
    half_p = lz.mul(lz.add(a, sn), inv2)
    half_m = lz.mul(lz.sub(a, sn), inv2)

    # L2: stacked (p+1)/4 lanes: [x_p, x_m]
    l2 = _pow_lanes(tw._lane_stack([half_p, half_m]), _E_SQRT)
    x_p, x_m = tw._unstack(l2, 2)
    xp_ok = _fq_eq(lz.mul(x_p, x_p), half_p) & ~_fq_is_zero(x_p)
    xm_ok = _fq_eq(lz.mul(x_m, x_m), half_m) & ~_fq_is_zero(x_m)

    # L3: stacked p-2 lanes: [inv(2x_p), inv(2x_m)]
    l3 = _pow_lanes(tw._lane_stack([lz.dbl(x_p), lz.dbl(x_m)]), _E_INV)
    ixp, ixm = tw._unstack(l3, 2)
    y_p = lz.mul(b, ixp)
    y_m = lz.mul(b, ixm)

    cand_p = _mk2(x_p, y_p)
    cand_m = _mk2(x_m, y_m)
    cp_ok = xp_ok & _fq2_eq(tw.fq2_sqr(cand_p), v)
    cm_ok = xm_ok & _fq2_eq(tw.fq2_sqr(cand_m), v)
    gen_root = gj._sel(cp_ok, cand_p, cand_m)
    gen_ok = sn_ok & (cp_ok | cm_ok)

    root = gj._sel(b_zero, out_bz, gen_root)
    ok = jnp.where(b_zero, True, gen_ok)
    # v == 0: root 0, ok
    zero2 = LF(jnp.zeros_like(root.v), 0, 0)
    root = gj._sel(v_zero, zero2, root)
    return root, ok


# ------------------------------------------------------------- SSWU ------


def _bc2(arr: np.ndarray, like: LF) -> LF:
    return lf(jnp.broadcast_to(jnp.asarray(arr), like.v.shape), val=P_INT - 1)


def _map_to_curve_sswu(u: LF) -> tuple[LF, LF]:
    """Affine (x', y') on E2' for a batch of field elements — the exact
    branch structure of map_to_curve_sswu_g2, lane-selected."""
    A = _bc2(_A_L, u)
    B = _bc2(_B_L, u)
    Z = _bc2(_Z_L, u)
    one = _bc2(_ONE2_L, u)

    u2 = tw.fq2_sqr(u)
    tv1 = tw.fq2_mul(Z, u2)
    tv2 = tw.fq2_add(tw.fq2_sqr(tv1), tv1)
    tv2_zero = tw.fq2_is_zero(tv2)

    # regular x1 = (-B/A) * (1 + tv2^-1); tv2^-1 via conj/norm with one
    # Fq exponent lane (p-2)
    t_a, t_b = _c0(tv2), _c1(tv2)
    tnorm = lz.add(lz.mul(t_a, t_a), lz.mul(t_b, t_b))
    # guard the zero lane so pow doesn't see 0 (its result is discarded)
    one_fq = lf(jnp.broadcast_to(jnp.asarray(lz.ONE_MONT), tnorm.v.shape), val=P_INT - 1)
    tnorm_safe = _self_fq(tv2_zero, one_fq, tnorm)
    tni = _pow_lanes(tnorm_safe, _E_INV)
    tv2_inv = _mk2(lz.mul(t_a, tni), lz.mul(lz.sub(LF(jnp.zeros_like(t_b.v), 0, 0), t_b), tni))
    x1_reg = tw.fq2_mul(_bc2(_NEG_B_OVER_A_L, u), tw.fq2_add(one, tv2_inv))
    x1_exc = _bc2(_B_OVER_ZA_L, u)
    x1 = gj._sel(tv2_zero, x1_exc, x1_reg)

    def gx(x: LF) -> LF:
        return tw.fq2_add(
            tw.fq2_mul(tw.fq2_add(tw.fq2_sqr(x), A), x), B
        )

    gx1 = gx(x1)
    x2 = tw.fq2_mul(tv1, x1)
    gx2 = gx(x2)

    y1, ok1 = _fq2_sqrt_batch(gx1)
    y2, _ok2 = _fq2_sqrt_batch(gx2)  # one of the two always succeeds

    x = gj._sel(ok1, x1, x2)
    y = gj._sel(ok1, y1, y2)

    flip = _sgn0(u) != _sgn0(y)
    y = gj._sel(flip, tw.fq2_neg(y), y)
    return x, y


def _iso_map_jacobian(x: LF, y: LF) -> gj.G2J:
    """3-isogeny E2' -> E2 into Jacobian coordinates without inversions:
    Z = xd*yd, X = xn*xd*yd^2, Y = y*yn*xd^3*yd^2.  Poles (xd or yd == 0)
    land on Z == 0 = infinity, matching the host's kernel convention."""
    def horner(karr: np.ndarray, xx: LF) -> LF:
        acc = _bc2(karr[-1], xx)
        for i in range(karr.shape[0] - 2, -1, -1):
            acc = tw.fq2_add(tw.fq2_mul(acc, xx), _bc2(karr[i], xx))
        return acc

    xn = horner(_K_LIMBS[0], x)
    xd = horner(_K_LIMBS[1], x)
    yn = horner(_K_LIMBS[2], x)
    yd = horner(_K_LIMBS[3], x)

    z = tw.fq2_mul(xd, yd)
    yd2 = tw.fq2_sqr(yd)
    X = tw.fq2_mul(tw.fq2_mul(xn, xd), yd2)
    xd2 = tw.fq2_sqr(xd)
    Y = tw.fq2_mul(
        tw.fq2_mul(tw.fq2_mul(y, yn), tw.fq2_mul(xd2, xd)), yd2
    )
    return gj.G2J(X, Y, z)


# ------------------------------------------------------------ public API --


# The pipeline is split into TWO jits on purpose: one monolithic graph
# (sswu x2 + cofactor ladder) was measured to blow XLA's optimization
# passes past 20 GB on CPU.  Stage 1 evaluates BOTH field elements of
# every message through a single SSWU/isogeny body (stacked lanes) and
# adds the pair; stage 2 runs the cofactor ladder and converts to
# affine.  Two device dispatches per batch.


@jax.jit
def _h2c_map(u_limbs: jnp.ndarray):
    """[B, 2, 2, 15] field elements (two per message) -> Jacobian sum
    arrays for the B messages."""
    n = u_limbs.shape[0]
    stacked = jnp.concatenate([u_limbs[:, 0], u_limbs[:, 1]], axis=0)
    x, y = _map_to_curve_sswu(lf(stacked))
    pj = _iso_map_jacobian(x, y)
    p0 = gj.G2J(
        LF(pj.x.v[:n], pj.x.max, pj.x.val),
        LF(pj.y.v[:n], pj.y.max, pj.y.val),
        LF(pj.z.v[:n], pj.z.max, pj.z.val),
    )
    p1 = gj.G2J(
        LF(pj.x.v[n:], pj.x.max, pj.x.val),
        LF(pj.y.v[n:], pj.y.max, pj.y.val),
        LF(pj.z.v[n:], pj.z.max, pj.z.val),
    )
    summed = gj.g2_add(p0, p1)
    return (
        gj._canon(summed.x).v,
        gj._canon(summed.y).v,
        gj._canon(summed.z).v,
    )


@jax.jit
def _h2c_finish(xj: jnp.ndarray, yj: jnp.ndarray, zj: jnp.ndarray):
    """Jacobian sums -> cofactor-cleared affine limbs + infinity mask."""
    p = gj.G2J(lf(xj), lf(yj), lf(zj))
    cleared = gj.g2_clear_cofactor(p)
    ax, ay, inf = gj.g2_to_affine(cleared)
    return _canon_fq(ax), _canon_fq(ay), inf


def _h2c_core(u_limbs: jnp.ndarray):
    return _h2c_finish(*_h2c_map(u_limbs))


def hash_to_g2_device(msgs: list[bytes], dst: bytes = h2c.DST_G2):
    """Batched device hash-to-G2 — value-equal to the host hash_to_g2 for
    every message.  Returns a list of crypto.curve.Point.

    The batch is padded to the next power of two (extra lanes hash a
    fixed dummy message) so the compile-heavy jits serve every batch size
    from a handful of executables — the same same-pow2 sharing the G1 MSM
    kernel uses — instead of retracing per distinct message count."""
    from eth_consensus_specs_tpu.crypto.curve import B2, Point

    if not msgs:
        return []
    padded = 1 << (len(msgs) - 1).bit_length()
    rows = np.zeros((padded, 2, 2, lz.N_LIMBS), np.uint64)
    for i in range(padded):
        m = msgs[i] if i < len(msgs) else b"\x00pad"
        u0, u1 = h2c.hash_to_field_fq2(bytes(m), 2, dst)
        rows[i] = np.stack([tw.fq2_to_limbs(u0), tw.fq2_to_limbs(u1)])
    ax, ay, inf = _h2c_core(jnp.asarray(rows))
    ax_h, ay_h, inf_h = np.asarray(ax), np.asarray(ay), np.asarray(inf)
    out = []
    for i in range(len(msgs)):
        if inf_h[i]:
            out.append(Point.infinity(B2))
            continue
        out.append(
            Point(tw.limbs_to_fq2(ax_h[i]), tw.limbs_to_fq2(ay_h[i]), B2)
        )
    return out
