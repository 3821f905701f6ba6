"""Lazy-reduction BLS12-381 base-field limbs for the device (u64 lanes).

The first-generation Fq kernel (13x30-bit limbs, a `lax.scan` per carry
sweep; gone since G1 moved here) normalized limbs after EVERY add/sub — a
~130-node carry/borrow subgraph per operation that made pairing-sized XLA
graphs take minutes to compile (measured: 53s for ONE Fq12 product, while
a plain 400-op u64 chain compiles in 0.8s), and on the chip each of its
loop trips cost about what a whole fused multiply costs here. Every Fq
kernel (G1, G2, the tower, the pairing, hash-to-curve) runs on this
module; ops/field_limbs.py keeps only the 13x30-bit packed form that
crosses a G1 program's boundary.

This module keeps limbs LAZY, the way hand-written pairing libraries
(blst/RELIC) do, with every bound tracked STATICALLY at trace time:

* 15 x 26-bit limbs in u64 lanes; R = 2^390. Normalized limbs < 2^26
  leave 38 bits of lane headroom.
* ``add`` is ONE vector add — no carry propagation.
* ``sub`` is borrow-free: x + (F - y), where F is c*p re-encoded with
  every limb >= y's static per-limb bound (the lend trick
  f_i += k*2^26 - k preserves the value exactly); 2 vector ops.
* ``mul`` is Montgomery SOS. Preconditions checked against the STATIC
  bounds (Python ints riding along at trace time, zero graph cost):
  - product columns: N * (max_a+1) * (max_b+1) < 2^64  (lane overflow)
  - values:          val_a * val_b < p * R              (output < 2p)
  Violations auto-insert a carry sweep (``norm``) or a conditional-
  subtraction chain (``shrink``) — rare, because most tower formulas
  chain only 2-5 lazy ops between multiplies.

Every element is an ``LF`` (array + static max-limb + static value
bound). LF objects live INSIDE traced functions only; jit boundaries
pass raw arrays.
"""

from __future__ import annotations

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax.numpy as jnp

from eth_consensus_specs_tpu.crypto.fields import P as P_INT

LIMB_BITS = 26
N_LIMBS = 15  # 15 * 26 = 390 >= 381
MASK = (1 << LIMB_BITS) - 1
R_INT = 1 << (LIMB_BITS * N_LIMBS)  # 2^390
N0_INV = (-pow(P_INT, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)

NORM_MAX = MASK
P_TOP = P_INT >> (LIMB_BITS * (N_LIMBS - 1))  # top limb of p (~2^17)

_U = jnp.uint64


def int_to_limbs(x: int) -> np.ndarray:
    out = np.zeros(N_LIMBS, np.uint64)
    for i in range(N_LIMBS):
        out[i] = (x >> (LIMB_BITS * i)) & MASK
    return out


def limbs_to_int(arr) -> int:
    a = np.asarray(arr, np.uint64)
    return sum(int(a[i]) << (LIMB_BITS * i) for i in range(len(a)))


def to_mont(x: int) -> np.ndarray:
    return int_to_limbs((x * R_INT) % P_INT)


def from_mont_int(limbs) -> int:
    return (limbs_to_int(limbs) * pow(R_INT, -1, P_INT)) % P_INT


P_LIMBS = int_to_limbs(P_INT)
ONE_MONT = to_mont(1)


class LF:
    """Limb array [..., 15] u64 + static (max_limb, value) bounds."""

    __slots__ = ("v", "max", "val")

    def __init__(self, v, max_limb: int, val: int):
        self.v = v
        self.max = max_limb
        self.val = val


def lf(v, val: int | None = None) -> LF:
    """Wrap a normalized-limb array. Default value bound 2p (Montgomery
    outputs live in [0, 2p); host conversions are < p)."""
    return LF(v, NORM_MAX, (2 * P_INT - 1) if val is None else val)


def zero_like(x: LF) -> LF:
    return LF(jnp.zeros_like(x.v), 0, 0)


def add(x: LF, y: LF) -> LF:
    if x.val + y.val >= R_INT // 4:
        x = shrink(x) if x.val >= y.val else x
        y = shrink(y) if x.val < y.val else y
    return LF(x.v + y.v, x.max + y.max, x.val + y.val)


def dbl(x: LF) -> LF:
    return LF(x.v + x.v, 2 * x.max, 2 * x.val)


# --- borrow-free subtraction ----------------------------------------------

_FAT_CACHE: dict[tuple[int, int], tuple[np.ndarray, int, int]] = {}


def _fat_p(limb_bound: int, top_bound: int) -> tuple[np.ndarray, int, int]:
    """c*p re-encoded with middle/low limbs >= limb_bound and the top
    limb >= top_bound; value is exactly c*p. Returns (limbs, max_limb, c)."""
    k = (limb_bound >> LIMB_BITS) + 2  # lend amount per position
    c = max((top_bound + k) // P_TOP + 1, 1)
    key = (limb_bound, top_bound)
    hit = _FAT_CACHE.get(key)
    if hit is not None:
        return hit
    while True:
        base = c * P_INT
        digits = []
        rem = base
        for _ in range(N_LIMBS):
            digits.append(rem & MASK)
            rem >>= LIMB_BITS
        if rem != 0:
            raise AssertionError("fat multiple exceeds 15 limbs — bound too large")
        f = [0] * N_LIMBS
        f[0] = digits[0] + (k << LIMB_BITS)
        for i in range(1, N_LIMBS - 1):
            f[i] = digits[i] + (k << LIMB_BITS) - k
        f[N_LIMBS - 1] = digits[N_LIMBS - 1] - k
        if f[N_LIMBS - 1] >= top_bound and all(
            f[i] >= limb_bound for i in range(N_LIMBS - 1)
        ):
            break
        c += 1
    assert sum(v << (LIMB_BITS * i) for i, v in enumerate(f)) == base
    arr = np.array(f, np.uint64)
    hit = (arr, max(f), c)
    _FAT_CACHE[key] = hit
    return hit


# Static cap on any _fat_p limb (f_i ~ y.max + 3*2^26 for every real
# call site). The rangelint lend-path Wrap declares the SAME cap
# (analysis/kernels.py), so the abstract interpreter's trusted bound for
# `fat - y` and this trace-time assertion can never drift apart; a limb
# this size leaves 2^34 of lane headroom for the subsequent add/mul
# columns (15 * 2^30 * 2^26 < 2^60).
_LEND_LIMB_CAP = 1 << 30


def sub(x: LF, y: LF) -> LF:
    """x - y (mod p), borrow-free against y's static bounds. A very lazy
    subtrahend would force a fat multiple with a huge top-limb cover
    (c ~ y_top/p_top), escalating the value bound — shrink first instead
    (the static bounds make this a rare, trace-time decision)."""
    if y.val > 16 * P_INT:
        y = shrink(y)
    if x.val > R_INT // 4:
        x = shrink(x)
    top_bound = min(y.max, y.val >> (LIMB_BITS * (N_LIMBS - 1)))
    fat, fat_max, c = _fat_p(y.max, top_bound)
    if fat_max > _LEND_LIMB_CAP:
        # a subtrahend can be lazy enough to outgrow the lend cap without
        # tripping the val-triggered shrink above (a 15-term canonical sum:
        # val = 15p < 16p, but max ~15*2^26 pushes the fat cover past 2^30)
        # — auto-insert the sweep, per the module contract, and re-cover
        y = shrink(y)
        top_bound = min(y.max, y.val >> (LIMB_BITS * (N_LIMBS - 1)))
        fat, fat_max, c = _fat_p(y.max, top_bound)
    # bound growth on the lend path: the fat limbs must respect the cap
    # the range analysis trusts, and the x + (fat - y) add must be
    # provably in-lane — neither held by construction before
    assert fat_max <= _LEND_LIMB_CAP, (
        f"_fat_p limb {fat_max} exceeds the declared lend cap "
        f"{_LEND_LIMB_CAP} even after shrink"
    )
    assert x.max + fat_max < (1 << 64), "sub: x + (fat - y) could wrap the lane"
    diff = jnp.asarray(fat) - y.v
    return LF(x.v + diff, x.max + fat_max, x.val + c * P_INT)


# --- normalization ---------------------------------------------------------


def norm(x: LF) -> LF:
    """Carry sweep to limbs < 2^26. Value must be < R (asserted
    statically) so the top carry is provably zero."""
    assert x.val < R_INT, "norm: value bound reached R — shrink first"
    if x.max <= NORM_MAX:
        return x
    out = []
    carry = None
    for i in range(N_LIMBS):
        cur = x.v[..., i] if carry is None else x.v[..., i] + carry
        out.append(cur & _U(MASK))
        carry = cur >> _U(LIMB_BITS)
    # top carry == 0 because val < 2^390
    return LF(jnp.stack(out, axis=-1), NORM_MAX, x.val)


def _geq(a, b_arr):
    acc = jnp.ones(a.shape[:-1], dtype=bool)
    for i in range(N_LIMBS):
        x, y = a[..., i], b_arr[..., i]
        acc = jnp.where(x == y, acc, x > y)
    return acc


def _sub_limbs(a, b_arr):
    out = []
    borrow = None
    for i in range(N_LIMBS):
        cur = a[..., i] - b_arr[..., i]
        if borrow is not None:
            cur = cur - borrow
        under = cur >> _U(63)
        out.append(cur + (under << _U(LIMB_BITS)))
        borrow = under
    return jnp.stack(out, axis=-1)


def shrink(x: LF) -> LF:
    """Reduce the VALUE below 2p via norm + a conditional-subtraction
    chain of power-of-two multiples of p (each halves the bound)."""
    x = norm(x)
    bound = x.val
    v = x.v
    while bound >= 2 * P_INT:
        # smallest m = 2^j * p with 2m >= bound: cond-sub leaves value < m
        q = (bound + P_INT - 1) // P_INT
        j = (q - 1).bit_length() - 1
        m = (1 << j) * P_INT
        assert 2 * m >= bound and m < bound and m < R_INT
        mb = jnp.broadcast_to(jnp.asarray(int_to_limbs(m)), v.shape)
        take = _geq(v, mb)
        v = jnp.where(take[..., None], _sub_limbs(v, mb), v)
        bound = m
    return LF(v, NORM_MAX, bound)


# --- Montgomery multiplication --------------------------------------------

_LANE_BUDGET = (1 << 64) - (1 << 40)  # carry slack


def _fix_operand(x: LF, y: LF) -> tuple[LF, LF]:
    """Insert norm/shrink so mul preconditions hold (static decision)."""
    # value precondition: val_x * val_y < p * R
    while x.val * y.val >= P_INT * R_INT:
        if x.val >= y.val:
            x = shrink(x)
        else:
            y = shrink(y)
    # lane precondition
    if N_LIMBS * (x.max + 1) * (y.max + 1) >= _LANE_BUDGET:
        if x.max >= y.max:
            x = norm(x)
        else:
            y = norm(y)
    if N_LIMBS * (x.max + 1) * (y.max + 1) >= _LANE_BUDGET:
        if x.max >= y.max:
            x = norm(x)
        else:
            y = norm(y)
    assert N_LIMBS * (x.max + 1) * (y.max + 1) < _LANE_BUDGET
    return x, y


def mul(x: LF, y: LF) -> LF:
    """Montgomery product x*y*R^-1 mod p; output normalized, < 2p.

    Same SOS algorithm with deferred carries as always, but built from
    VECTOR ops over the limb axis — one outer product plus shifted-slice
    accumulates — instead of 225 per-limb scalar multiplies.  That cuts
    the XLA graph ~4x per multiply, which is what makes the big unrolled
    curve kernels (pairing, hash-to-curve) compile in sane time; the
    arithmetic (and therefore every carry/overflow bound) is unchanged."""
    x, y = _fix_operand(x, y)
    mask = _U(MASK)
    shift = _U(LIMB_BITS)
    n0 = _U(N0_INV)
    W = 2 * N_LIMBS + 1

    def _pad_to(vrow, lo: int):
        """Place a [..., n] row at column offset `lo` of a width-W vector
        (jnp.pad, never scatter — scatter lowering dominates compile)."""
        n = vrow.shape[-1]
        return jnp.pad(vrow, [(0, 0)] * (vrow.ndim - 1) + [(lo, W - lo - n)])

    outer = x.v[..., :, None] * y.v[..., None, :]  # [..., 15, 15]
    t = _pad_to(outer[..., 0, :], 0)
    for i in range(1, N_LIMBS):
        # column k = i + j accumulates a_i * b_j: row i lands at offset i
        t = t + _pad_to(outer[..., i, :], i)

    # ONE vector carry round caps every column at mask + (budget >> 26)
    # < 2^39 — exact residue per column is preserved (value semantics),
    # and the deferred-carry folds below keep m-digit reads correct.
    t = (t & mask) + _pad_to(t[..., :-1] >> shift, 1)

    pv = jnp.asarray(P_LIMBS)
    for i in range(N_LIMBS):
        # mask BEFORE the n0 product: (t_i & mask) * n0 ≡ t_i * n0
        # (mod 2^26), so m is unchanged — but the unmasked product could
        # exceed 2^64 and leaned on silent u64 truncation for its low
        # bits; pre-masking keeps every intermediate provably in-lane
        # (rangelint lane-overflow, ~2^52 vs ~2^78)
        m = ((t[..., i] & mask) * n0) & mask
        t = t + _pad_to(m[..., None] * pv, i)
        # fold position i's full value upward before step i+1 reads i+1
        t = t + _pad_to((t[..., i] >> shift)[..., None], i + 1)

    out = []
    carry = None
    for j in range(N_LIMBS, W):
        cur = t[..., j] if carry is None else t[..., j] + carry
        if len(out) < N_LIMBS:
            out.append(cur & mask)
        carry = cur >> shift
    return LF(jnp.stack(out, axis=-1), NORM_MAX, 2 * P_INT - 1)


def is_zero(x: LF):
    """True iff x == 0 mod p: the normalized limbs are those of a
    multiple of p below the static value bound (0 or p for a mont
    output, value < 2p)."""
    n = norm(x)
    hit = jnp.all(n.v == 0, axis=-1)
    for k in range(1, x.val // P_INT + 1):
        hit = hit | jnp.all(n.v == jnp.asarray(int_to_limbs(k * P_INT)), axis=-1)
    return hit
