"""Parameterized fixed-limb modular arithmetic for the device.

30-bit limbs in uint64 lanes for any odd modulus: a 30x30-bit partial
product is < 2^60 and a column of such products plus carries stays under
2^64, so schoolbook accumulation never overflows a lane. The product is
an unrolled pad-shift-add (NOT a dot/einsum: XLA:TPU cannot lower a u64
dot_general), the Montgomery (SOS) reduction a lax.scan, values kept in
the redundant range [0, 2p) (R > 4p, so a Montgomery output needs no
conditional subtraction).  The BLS *scalar* field instance (9x30-bit
limbs for the 255-bit r) backs the DAS FFT kernel (ops/fr_fft.py); Fq
runs on ops/lazy_limbs.
"""

from __future__ import annotations

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax.numpy as jnp
from jax import lax

LIMB_BITS = 30
MASK = (1 << LIMB_BITS) - 1
U64 = jnp.uint64


class LimbField:
    """Montgomery limb arithmetic mod an odd `modulus` with the smallest
    limb count whose radix R = 2^(30*k) exceeds 4*modulus."""

    def __init__(self, modulus: int):
        assert modulus % 2 == 1
        n_limbs = (modulus.bit_length() + LIMB_BITS) // LIMB_BITS
        while (1 << (LIMB_BITS * n_limbs)) <= 4 * modulus:
            n_limbs += 1
        self.modulus = modulus
        self.n_limbs = n_limbs
        self.r_int = 1 << (LIMB_BITS * n_limbs)
        # 64-bit words that hold every limb: the host's array conversions
        self.n_words = -(-LIMB_BITS * n_limbs // 64)
        self.n0_inv = (-pow(modulus, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)
        self.p_limbs = self.int_to_limbs(modulus)
        self.p2_limbs = self.int_to_limbs(2 * modulus)
        self.one_mont = self.to_mont(1)

    # -- host conversions --------------------------------------------------

    def int_to_limbs(self, x: int) -> np.ndarray:
        out = np.zeros(self.n_limbs, np.uint64)
        for i in range(self.n_limbs):
            out[i] = x & MASK
            x >>= LIMB_BITS
        assert x == 0
        return out

    def limbs_to_int(self, arr) -> int:
        x = 0
        for i in reversed(range(self.n_limbs)):
            x = (x << LIMB_BITS) | int(arr[i])
        return x

    def to_mont(self, x: int) -> np.ndarray:
        return self.int_to_limbs((x * self.r_int) % self.modulus)

    def from_mont_int(self, limbs) -> int:
        raw = self.limbs_to_int(np.asarray(limbs))
        return (raw * pow(self.r_int, -1, self.modulus)) % self.modulus

    def ints_to_mont_batch(self, values) -> np.ndarray:
        """[...,] python ints -> [..., n_limbs] Montgomery u64 limbs."""
        flat = [self.to_mont(int(v) % self.modulus) for v in np.ravel(np.asarray(values, dtype=object))]
        out = np.stack(flat).reshape((*np.shape(values), self.n_limbs))
        return out

    def mont_batch_to_ints(self, limbs) -> list[int]:
        arr = np.asarray(limbs)
        flat = arr.reshape(-1, self.n_limbs)
        return [self.from_mont_int(row) for row in flat]

    # The array forms of int_to_limbs / limbs_to_int: a limb is a bit
    # field of the integer, so a whole batch is cut from (joined into)
    # little-endian words with one shift and mask a limb.

    def words_to_limbs(self, words):
        """[..., k] little-endian words of 32 or 64 bits, a NumPy or a
        device array -> [..., n_limbs] u64 limbs: limb i is bits
        [30 i, 30 i + 30) of the integer the words spell. The ONE cutter of
        every boundary: the host's integers pass through it as 64-bit
        words (:meth:`ints_to_limbs_batch`), a data column flush's bytes
        as 32-bit words inside its device program (ops/fr_fft.py). Bits
        the words do not hold are zero; the caller answers for bits above
        the last limb."""
        bits = 8 * words.dtype.itemsize
        word = words.dtype.type
        limbs = []
        for i in range(self.n_limbs):
            q, o = divmod(i * LIMB_BITS, bits)
            limb = words[..., q] >> word(o)
            if o + LIMB_BITS > bits and q + 1 < words.shape[-1]:
                limb = limb | (words[..., q + 1] << word(bits - o))
            limbs.append((limb & word(MASK)).astype(np.uint64, copy=False))
        xp = np if isinstance(words, np.ndarray) else jnp
        return xp.stack(limbs, axis=-1)

    def ints_to_limbs_batch(self, values) -> np.ndarray:
        """Flat sequence of ints in [0, 2^(30 * n_limbs)) -> [len, n_limbs]
        u64 limbs, row i equal to ``int_to_limbs(values[i])``."""
        buf = b"".join([v.to_bytes(8 * self.n_words, "little") for v in values])
        words = np.frombuffer(buf, dtype="<u8").reshape(-1, self.n_words)
        # int_to_limbs' `assert x == 0`: no bit above the last limb
        top = LIMB_BITS * self.n_limbs - 64 * (self.n_words - 1)
        assert top == 64 or not (words[:, -1] >> np.uint64(top)).any()
        return self.words_to_limbs(words)

    def limbs_to_ints_batch(self, limbs) -> list[int]:
        """[..., n_limbs] limbs, each below 2^30 -> flat list of ints,
        entry i equal to ``limbs_to_int`` of row i."""
        arr = np.asarray(limbs, np.uint64).reshape(-1, self.n_limbs)
        words = np.zeros((arr.shape[0], self.n_words), np.uint64)
        for k in range(self.n_limbs):
            q, o = divmod(k * LIMB_BITS, 64)
            words[:, q] |= arr[:, k] << np.uint64(o)
            if o + LIMB_BITS > 64:
                words[:, q + 1] |= arr[:, k] >> np.uint64(64 - o)
        buf = memoryview(words.astype("<u8", copy=False).tobytes())
        step = 8 * self.n_words
        return [
            int.from_bytes(buf[i : i + step], "little")
            for i in range(0, len(buf), step)
        ]

    # -- device ops (shape-generic over leading axes) ----------------------

    def _limb_product(self, a, b):
        partials = a[..., :, None] * b[..., None, :]
        batch_pad = [(0, 0)] * (partials.ndim - 2)
        out = None
        for i in range(self.n_limbs):
            row = jnp.pad(partials[..., i, :], batch_pad + [(i, self.n_limbs - 1 - i)])
            out = row if out is None else out + row
        return out

    @staticmethod
    def _carry_sweep(t):
        tT = jnp.moveaxis(t, -1, 0)

        def step(carry, col):
            cur = col + carry
            return cur >> jnp.uint64(LIMB_BITS), cur & jnp.uint64(MASK)

        carry, cols = lax.scan(step, jnp.zeros_like(tT[0]), tT)
        return jnp.moveaxis(cols, 0, -1), carry

    @staticmethod
    def _geq(a, b):
        aT = jnp.moveaxis(a, -1, 0)
        bT = jnp.moveaxis(b, -1, 0)

        def step(acc, ab):
            x, y = ab
            acc = jnp.where(x == y, acc, x > y)
            return acc, None

        acc, _ = lax.scan(step, jnp.ones_like(aT[0], dtype=bool), (aT, bT))
        return acc

    @staticmethod
    def _sub_limbs(a, b):
        aT = jnp.moveaxis(a, -1, 0)
        bT = jnp.moveaxis(b, -1, 0)

        def step(borrow, ab):
            x, y = ab
            cur = x - y - borrow
            under = cur >> jnp.uint64(63)
            return under, cur + (under << jnp.uint64(LIMB_BITS))

        _, cols = lax.scan(step, jnp.zeros_like(aT[0]), (aT, bT))
        return jnp.moveaxis(cols, 0, -1)

    def _cond_sub(self, t, bound_limbs):
        bound = jnp.asarray(bound_limbs)
        b = jnp.broadcast_to(bound, t.shape)
        need = self._geq(t, b)
        sub = self._sub_limbs(t, b)
        return jnp.where(need[..., None], sub, t)

    def mont_mul(self, a, b):
        """abR^-1 mod p for a, b in [0, 2p); result in [0, 2p)."""
        n = self.n_limbs
        mask = jnp.uint64(MASK)
        shift = jnp.uint64(LIMB_BITS)
        n0 = jnp.uint64(self.n0_inv)
        p_vec = jnp.asarray(self.p_limbs)

        prod = self._limb_product(a, b)
        t, carry = self._carry_sweep(prod)
        t = jnp.concatenate(
            [t, carry[..., None], jnp.zeros_like(carry)[..., None]], axis=-1
        )

        def red_step(t, i):
            ti = lax.dynamic_slice_in_dim(t, i, 1, axis=-1)[..., 0]
            m = ((ti & mask) * n0) & mask
            window = lax.dynamic_slice_in_dim(t, i, n, axis=-1)
            window = window + m[..., None] * p_vec
            t = lax.dynamic_update_slice_in_dim(t, window, i, axis=-1)
            pair = lax.dynamic_slice_in_dim(t, i, 2, axis=-1)
            folded = jnp.stack(
                [pair[..., 0] & mask, pair[..., 1] + (pair[..., 0] >> shift)], axis=-1
            )
            return lax.dynamic_update_slice_in_dim(t, folded, i, axis=-1), None

        t, _ = lax.scan(red_step, t, jnp.arange(n, dtype=jnp.int32))
        res, _carry = self._carry_sweep(t[..., n : 2 * n + 1])
        return res[..., :n]

    def add_mod(self, a, b):
        t, _carry = self._carry_sweep(a + b)
        return self._cond_sub(t, self.p2_limbs)

    def sub_mod(self, a, b):
        p2 = jnp.broadcast_to(jnp.asarray(self.p2_limbs), b.shape)
        t, _ = self._carry_sweep(a + self._sub_limbs(p2, b))
        return self._cond_sub(t, self.p2_limbs)
