"""The packed form of a BLS12-381 base-field element at the boundary of
every G1 device program: host constants and conversions, no device code.

An Fq element crosses into and out of a G1 program (ops/g1_msm), and
lies in the resident key table (ops/key_table), as 13 x 30-bit
Montgomery limbs in uint64 lanes, R = 2^390, value in the REDUNDANT range
[0, 2p) (`from_mont_int` canonicalizes at the host boundary). The
arithmetic itself runs on ops/lazy_limbs (15 x 26-bit limbs, the same R),
which a program regroups to at entry and from at exit; the device half
this module once had (a multiply, an add and a subtract built from
`lax.scan`s over the limbs) is gone. Two limb layers are left:
`lazy_limbs` for Fq, `limb_field.LimbField` for Fr.
"""

from __future__ import annotations

import numpy as np

from eth_consensus_specs_tpu.crypto.fields import P as P_INT

LIMB_BITS = 30
N_LIMBS = 13  # 13 * 30 = 390 >= 381
MASK = (1 << LIMB_BITS) - 1
R_INT = 1 << (LIMB_BITS * N_LIMBS)  # Montgomery radix 2^390 (> 4p)

def int_to_limbs(x: int) -> np.ndarray:
    out = np.zeros(N_LIMBS, np.uint64)
    for i in range(N_LIMBS):
        out[i] = (x >> (LIMB_BITS * i)) & MASK
    return out


def _limbs_to_int(arr: np.ndarray) -> int:
    return sum(limb << (LIMB_BITS * i) for i, limb in enumerate(arr.tolist()))


def to_mont(x: int) -> np.ndarray:
    """Host: canonical int -> Montgomery-form limbs (x * R mod p)."""
    return int_to_limbs((x * R_INT) % P_INT)


R_INV_INT = pow(R_INT, -1, P_INT)


def from_mont_int(limbs) -> int:
    """Host: (possibly redundant) Montgomery limbs -> canonical int."""
    x = _limbs_to_int(np.asarray(limbs, np.uint64))
    return (x * R_INV_INT) % P_INT


ONE_MONT = to_mont(1)
