"""Fixed-limb BLS12-381 base-field arithmetic for the device (uint64 lanes).

TPUs have no wide-integer units, so Fp (381-bit) elements live as 13x30-bit
limbs in uint64 lanes: a 30x30-bit partial product is <2^60 and a column of
13 such products plus carries stays under 2^64, so schoolbook accumulation
never overflows a lane. Multiplication is Montgomery (R = 2^390) in
separated (SOS) form: an unrolled pad-shift-add for the full 25-column
product (13 static rows — NOT a dot/einsum, which XLA:TPU cannot lower
for u64), then a 13-step lax.scan reduction — the graph stays ~100 HLO
ops per multiply (an unrolled CIOS was ~25x bigger and made XLA compile
times explode).

Values are kept in the REDUNDANT range [0, 2p): R > 4p, so Montgomery
outputs stay < 2p without any conditional subtraction, and only additions
pay one conditional 2p-subtraction. `from_mont_int` canonicalizes at the
host boundary.

This is the arithmetic layer the VERDICT's device-BLS step 1 calls for
(reference native analogue: the milagro/arkworks limb code behind
utils/bls.py:224-296). Host Python ints are the conversion boundary;
correctness oracles are crypto/fields.py and plain pow().
"""

from __future__ import annotations

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax.numpy as jnp
from jax import lax

from eth_consensus_specs_tpu.crypto.fields import P as P_INT

LIMB_BITS = 30
N_LIMBS = 13  # 13 * 30 = 390 >= 381
MASK = (1 << LIMB_BITS) - 1
R_INT = 1 << (LIMB_BITS * N_LIMBS)  # Montgomery radix 2^390 (> 4p)
# -P^-1 mod 2^30 (per-word quotient constant)
N0_INV = (-pow(P_INT, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)

U64 = jnp.uint64


def int_to_limbs(x: int) -> np.ndarray:
    out = np.zeros(N_LIMBS, np.uint64)
    for i in range(N_LIMBS):
        out[i] = (x >> (LIMB_BITS * i)) & MASK
    return out


def _limbs_to_int(arr: np.ndarray) -> int:
    return sum(limb << (LIMB_BITS * i) for i, limb in enumerate(arr.tolist()))


P_LIMBS = int_to_limbs(P_INT)
P2_LIMBS = int_to_limbs(2 * P_INT)


def to_mont(x: int) -> np.ndarray:
    """Host: canonical int -> Montgomery-form limbs (x * R mod p)."""
    return int_to_limbs((x * R_INT) % P_INT)


R_INV_INT = pow(R_INT, -1, P_INT)


def from_mont_int(limbs) -> int:
    """Host: (possibly redundant) Montgomery limbs -> canonical int."""
    x = _limbs_to_int(np.asarray(limbs, np.uint64))
    return (x * R_INV_INT) % P_INT


ONE_MONT = to_mont(1)


# == device kernels (elementwise over leading batch dims) ==================

def _limb_product(a, b):
    """Full 25-column schoolbook product, columns NOT carried.
    Column magnitude <= 13 * (2^30-1)^2 + carries < 2^64.

    The anti-diagonal accumulation is an unrolled pad-shift-add (13 static
    rows), NOT a dot/einsum: XLA:TPU cannot lower u64 dot_general ("u64
    dot" hits the unimplemented X64-rewrite path at compile time), while
    elementwise u64 multiplies/adds lower fine on every backend."""
    partials = a[..., :, None] * b[..., None, :]
    batch_pad = [(0, 0)] * (partials.ndim - 2)
    out = None
    for i in range(N_LIMBS):
        row = jnp.pad(partials[..., i, :], batch_pad + [(i, N_LIMBS - 1 - i)])
        out = row if out is None else out + row
    return out


def _carry_sweep(t):
    """Normalize limbs of t[..., L] to <2^30; returns (normalized, carry)."""
    tT = jnp.moveaxis(t, -1, 0)

    def step(carry, col):
        cur = col + carry
        return cur >> jnp.uint64(LIMB_BITS), cur & jnp.uint64(MASK)

    carry, cols = lax.scan(step, jnp.zeros_like(tT[0]), tT)
    return jnp.moveaxis(cols, 0, -1), carry


def _geq(a, b):
    """Lexicographic a >= b over [..., 13] limb arrays (4-op scan body)."""
    aT = jnp.moveaxis(a, -1, 0)
    bT = jnp.moveaxis(b, -1, 0)

    def step(acc, ab):
        x, y = ab
        # scanning least-significant first: a later (more significant)
        # difference overrides the accumulated verdict
        acc = jnp.where(x == y, acc, x > y)
        return acc, None

    acc, _ = lax.scan(step, jnp.ones_like(aT[0], dtype=bool), (aT, bT))
    return acc


def _sub_limbs(a, b):
    """a - b with borrow chain, assuming a >= b (scan over limbs)."""
    aT = jnp.moveaxis(a, -1, 0)
    bT = jnp.moveaxis(b, -1, 0)

    def step(borrow, ab):
        x, y = ab
        cur = x - y - borrow
        under = cur >> jnp.uint64(63)
        return under, cur + (under << jnp.uint64(LIMB_BITS))

    _, cols = lax.scan(step, jnp.zeros_like(aT[0]), (aT, bT))
    return jnp.moveaxis(cols, 0, -1)


def _cond_sub(t, bound_limbs):
    """Subtract `bound` once when t >= bound (t < 2*bound)."""
    bound = jnp.asarray(bound_limbs)
    b = jnp.broadcast_to(bound, t.shape)
    need = _geq(t, b)
    sub = _sub_limbs(t, b)
    return jnp.where(need[..., None], sub, t)


def mont_mul(a, b):
    """Montgomery product abR^-1 mod p for a, b in [0, 2p).
    Result in [0, 2p) — no conditional subtraction needed (R > 4p)."""
    mask = jnp.uint64(MASK)
    shift = jnp.uint64(LIMB_BITS)
    n0 = jnp.uint64(N0_INV)
    p_vec = jnp.asarray(P_LIMBS)

    prod = _limb_product(a, b)  # [..., 25]
    t, carry = _carry_sweep(prod)
    t = jnp.concatenate(
        [t, carry[..., None], jnp.zeros_like(carry)[..., None]], axis=-1
    )  # [..., 27]

    def red_step(t, i):
        ti = lax.dynamic_slice_in_dim(t, i, 1, axis=-1)[..., 0]
        m = ((ti & mask) * n0) & mask
        window = lax.dynamic_slice_in_dim(t, i, N_LIMBS, axis=-1)
        window = window + m[..., None] * p_vec
        t = lax.dynamic_update_slice_in_dim(t, window, i, axis=-1)
        # fold t[i]'s (now low-zero) value up as a carry
        pair = lax.dynamic_slice_in_dim(t, i, 2, axis=-1)
        folded = jnp.stack(
            [pair[..., 0] & mask, pair[..., 1] + (pair[..., 0] >> shift)], axis=-1
        )
        return lax.dynamic_update_slice_in_dim(t, folded, i, axis=-1), None

    t, _ = lax.scan(red_step, t, jnp.arange(N_LIMBS, dtype=jnp.int32))
    res, carry = _carry_sweep(t[..., N_LIMBS : 2 * N_LIMBS + 1])  # [..., 14]
    # value < 2p < 2^382 fits in 13 limbs; top limb and carry are zero
    return res[..., :N_LIMBS]


def mont_sqr(a):
    return mont_mul(a, a)


def add_mod(a, b):
    """(a + b) kept in [0, 2p) via one conditional 2p-subtraction."""
    t, carry = _carry_sweep(a + b)
    # inputs < 2p each -> sum < 4p < 2^383: top carry lands in limb 12's
    # sweep only if limbs were lazy; with <2^30 limbs carry is 0
    return _cond_sub(t, P2_LIMBS)


def sub_mod(a, b):
    """(a - b) kept in [0, 2p): a + (2p - b), then one cond-subtraction."""
    p2 = jnp.broadcast_to(jnp.asarray(P2_LIMBS), b.shape)
    t, _ = _carry_sweep(a + _sub_limbs(p2, b))
    return _cond_sub(t, P2_LIMBS)


def is_zero(a):
    """True iff the element is 0 mod p (redundant range: 0 or p)."""
    p = jnp.broadcast_to(jnp.asarray(P_LIMBS), a.shape)
    exact_zero = jnp.all(a == 0, axis=-1)
    exact_p = jnp.all(a == p, axis=-1)
    return exact_zero | exact_p
