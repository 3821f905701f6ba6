"""Swap-or-not shuffle in whole-permutation form.

The spec defines the shuffle per index: 90 hash-driven rounds deciding, for
each position, whether it swaps with its mirror around a per-round pivot
(reference: specs/phase0/beacon-chain.md:816-836; the reference then
LRU-caches the per-index loop, pysetup/spec_builders/phase0.py:59-88).

Inverted into whole-permutation form, each round is three vectorized steps
over ALL indices at once:
    flip  = (pivot - idx) mod n
    pos   = max(idx, flip)
    idx   = flip where bit(pos) else idx
with the decision bits gathered from one 32-byte hash per 256 positions.
The numpy path below is the host implementation; identity with the
per-index spec form is property-tested (tests/test_shuffle.py).

The device program (`shuffle_rounds_kernel`) turns the same rounds round:
it carries the LIST, not the indices. A round is an involution of
positions, `x <-> flip(x)`, and whether a pair swaps is read at the
pair's larger position: both are functions of the position alone. So
applied to a list, last round first, a round is
    new[x] = old[flip(x)] where bit(max(x, flip(x))) else old[x]
and `old[flip(x)]`, `flip(x) = (pivot - x) mod n`, is the list reversed
and rotated: two slices at a traced offset of the reversed list doubled,
no gather. The decision bit rides in the entry's sign bit through the same
reversal. After round 0 the list is `active[compute_shuffled_index(i)]`.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.obs import watchdog, waterfall


def shuffle_permutation(index_count: int, seed: bytes, rounds: int) -> np.ndarray:
    """perm[i] == compute_shuffled_index(i, index_count, seed) for all i."""
    if index_count == 0:
        return np.empty(0, dtype=np.int64)
    n = index_count
    idx = np.arange(n, dtype=np.int64)
    num_chunks = (n + 255) // 256
    sha = hashlib.sha256
    for rnd in range(rounds):
        rb = bytes([rnd])
        pivot = int.from_bytes(sha(seed + rb).digest()[:8], "little") % n
        # decision-bit sources: one hash per 256-position chunk
        src = np.frombuffer(
            b"".join(
                sha(seed + rb + (c).to_bytes(4, "little")).digest() for c in range(num_chunks)
            ),
            dtype=np.uint8,
        ).reshape(num_chunks, 32)
        flip = (pivot - idx) % n
        pos = np.maximum(idx, flip)
        byte_vals = src[pos // 256, (pos % 256) // 8]
        bits = (byte_vals >> (pos % 8).astype(np.uint8)) & 1
        idx = np.where(bits == 1, flip, idx)
    return idx


def shuffle_list(items: list, seed: bytes, rounds: int) -> list:
    """The shuffled sequence itself: out[i] = items[perm[i]]."""
    perm = shuffle_permutation(len(items), seed, rounds)
    return [items[int(p)] for p in perm]


# --- device program --------------------------------------------------------

# a decision message is seed (32 bytes) + round (1) + chunk counter (4,
# little-endian): 37 bytes, so its padded block ends in this bit length
_MESSAGE_BITS = 37 * 8


def mainnet_rounds() -> int:
    """`SHUFFLE_ROUND_COUNT` of the mainnet preset (90): the served verb's."""
    from eth_consensus_specs_tpu.config import load_preset

    return int(load_preset("mainnet", "phase0").SHUFFLE_ROUND_COUNT)


def _decision_digests(seed_words, rounds: int, chunks: int):
    """The digests of every (round, chunk) decision message, u32[rounds,
    chunks * 8] big-endian words, the padded blocks made here from the
    seed's eight words: the round byte and the chunk counter are counters,
    the padding and the bit length constants."""
    # imported where the program is traced: the spec's host path and the
    # vector generator import this module for the numpy form alone
    from .sha256 import sha256_single_block

    r = jax.lax.broadcasted_iota(jnp.uint32, (rounds, chunks), 0).reshape(-1)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rounds, chunks), 1).reshape(-1)
    # bytes 32..35: round, then the counter's three low bytes, low first
    w8 = (r << 24) | ((c & 0xFF) << 16) | (((c >> 8) & 0xFF) << 8) | ((c >> 16) & 0xFF)
    # bytes 36..39: the counter's high byte, the 0x80 delimiter
    w9 = ((c >> 24) << 24) | jnp.uint32(0x00800000)
    zero = jnp.zeros_like(r)
    words = [jnp.broadcast_to(seed_words[i], r.shape) for i in range(8)]
    words += [w8, w9, zero, zero, zero, zero, zero, jnp.full_like(r, _MESSAGE_BITS)]
    digests = sha256_single_block(jnp.stack(words, axis=-1))
    return digests.reshape(rounds, chunks * 8)


@jax.jit
def shuffle_rounds_kernel(seed_words, pivots, n, active):
    """`out[i] = active[compute_shuffled_index(i, n, seed)]` for i < n,
    `out[i] = active[i]` past it. One executable a lane bucket
    (`active.shape[0]`, a power of two) and round count
    (`pivots.shape[0]`): `n` is a traced number, the positions and chunks
    at or past it inert. seed_words u32[8] big-endian, pivots i32[rounds]
    (`hash(seed + round)[:8] % n`, the host's: a u64 remainder), active
    i32[lanes], every entry below 2**31 (the sign bit carries a decision
    bit through a round)."""
    lanes = active.shape[0]
    rounds = pivots.shape[0]
    chunks = -(-lanes // 256)
    digests = _decision_digests(seed_words, rounds, chunks)
    x = jax.lax.iota(jnp.int32, lanes)
    # where position p's decision bit sits in its digest word: byte p // 8
    # of the chunk's 32 (big-endian words), bit p % 8 of that byte
    bit_at = (8 * (3 - ((x >> 3) & 3)) + (x & 7)).astype(jnp.uint32)
    live = x < n

    def one_round(t, entries):
        # i32 loop bounds: python-int bounds widen the round counter (and
        # everything indexed by it) to i64 under the x64 flag
        r = jnp.int32(rounds - 1) - t
        pivot = pivots[r]
        words = jax.lax.dynamic_index_in_dim(digests, r, keepdims=False)
        bits = (jnp.repeat(words, 32)[:lanes] >> bit_at) & 1
        marked = entries.astype(jnp.uint32) | (bits << 31)
        mirror = marked[::-1]
        doubled = jnp.concatenate([mirror, mirror])
        # mirrored[x] = marked[flip(x)]: pivot - x up to the pivot,
        # pivot + n - x past it; reversed, each is one rotation
        low = jax.lax.dynamic_slice(doubled, (lanes - ((pivot + 1) & (lanes - 1)),), (lanes,))
        high = jax.lax.dynamic_slice(doubled, (lanes - ((pivot + 1 + n) & (lanes - 1)),), (lanes,))
        below = x <= pivot
        mirrored = jnp.where(below, low, high)
        flip = jnp.where(below, pivot - x, pivot + n - x)
        decides = jnp.where(x >= flip, marked, mirrored) >> 31
        swapped = (mirrored & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        return jnp.where(live & (decides == 1), swapped, entries)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(rounds), one_round, active)


def _pivots(seed: bytes, n: int, rounds: int) -> np.ndarray:
    """The rounds' pivots: a u64 remainder each, so the host's."""
    sha = hashlib.sha256
    return np.array(
        [int.from_bytes(sha(seed + bytes([r])).digest()[:8], "little") % n for r in range(rounds)],
        dtype=np.int32,
    )


def shuffled_indices_device(active: np.ndarray, seed: bytes, rounds: int,
                            lanes: int | None = None) -> np.ndarray:
    """`active[compute_shuffled_index(i, n, seed)]` for every i as int32[n],
    by ONE execution of `shuffle_rounds_kernel` at `lanes` (the count's
    bucket, `buckets.shuffle_key`, unless given): three legs, the pivots and the padded indices
    (`shuffle.pack`), transfer in and the program until the list is ready
    (`shuffle.call`), the list to the host cut to n (`shuffle.unpack`)."""
    from eth_consensus_specs_tpu.serve import buckets

    n = int(active.shape[0])
    if n == 0:
        return np.empty(0, np.int32)
    lanes = buckets.shuffle_key(n)[1] if lanes is None else int(lanes)
    if lanes < n or lanes & (lanes - 1):
        raise ValueError(f"{lanes} lanes: not a power of two that holds {n} indices")
    chunks = -(-n // 256)
    with waterfall.leg("shuffle.pack"):
        padded = np.zeros(lanes, np.int32)
        padded[:n] = active
        seed_words = np.frombuffer(seed, ">u4").astype(np.uint32)
        pivots = _pivots(seed, n, rounds)
    # lower-bound traffic: one compression per live decision hash (96 B)
    # plus the int32 list read and written every round
    work_bytes = 96 * rounds * chunks + 8 * n * rounds
    with obs.span("shuffle.permutation", work_bytes=work_bytes, lanes=n, rounds=rounds):
        with waterfall.leg("shuffle.call") as sp:
            sp.result = out = shuffle_rounds_kernel(seed_words, pivots, np.int32(n), padded)
    with waterfall.leg("shuffle.unpack"):
        shuffled = np.asarray(out)[:n]
    obs.count("shuffle.permutations", 1)
    obs.count("shuffle.lanes", n)
    obs.count("shuffle.decision_hashes", rounds * chunks)
    if watchdog.should_check("shuffle"):
        watchdog.check_shuffle_slice(shuffled, n, seed, rounds, active=active)
    return shuffled


def shuffle_permutation_device(index_count: int, seed: bytes, rounds: int) -> np.ndarray:
    """The whole permutation from the device program, bit-equal to
    shuffle_permutation / compute_shuffled_index: the shuffled list of
    0..n-1 is the permutation itself."""
    return shuffled_indices_device(np.arange(index_count, dtype=np.int32), seed, rounds)


def shuffled_indices_host(active: np.ndarray, seed: bytes, rounds: int) -> np.ndarray:
    """The same list as int32[n] by the host's numpy form."""
    return np.asarray(active, np.int32)[shuffle_permutation(len(active), seed, rounds)]


def shuffled_indices(active: np.ndarray, seed: bytes, rounds: int) -> np.ndarray:
    """The served shuffle: by the device program where `precompile` has
    compiled the count's lane bucket (`("shuffle", lanes)`), by the host's
    numpy form otherwise, so that no caller compiles on the serving thread.
    Same list either way."""
    from eth_consensus_specs_tpu.serve import buckets

    key = buckets.shuffle_key(len(active))
    if not buckets.is_compiled(*key):
        return shuffled_indices_host(active, seed, rounds)
    with buckets.first_dispatch(*key):
        return shuffled_indices_device(active, seed, rounds, lanes=key[1])
