"""The plain reference of an epoch's committee list: hashlib, numpy and
Python integers, nothing of the program imported.

Two forms of the same function. `compute_shuffled_index` is the spec's own
per-index walk (consensus-specs specs/phase0/beacon-chain.md
`compute_shuffled_index`; SHUFFLE_ROUND_COUNT 90 under the mainnet preset),
the truth, a few hundred microseconds a position: for sampled positions at a
mainnet registry and every position at a small one. `shuffled_list` makes
the whole list `[active[compute_shuffled_index(i, n, seed)] for i in
range(n)]` the way clients do (the in-place pair swap of the spec's
"optimized shuffle" note): the rounds last to first, and in each the list's
two mirrored stretches, up to the pivot and past it, swapped pair by pair
where the pair's decision bit, read at its LARGER position, is set. It
keeps no index plane and gathers nothing, so it shares no step with either
of the program's forms. ~2 s an epoch at 2**20.
"""

from __future__ import annotations

import hashlib

import numpy as np

SHUFFLE_ROUND_COUNT = 90  # presets/mainnet/phase0.yaml


def _hash(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def compute_shuffled_index(index: int, index_count: int, seed: bytes,
                           rounds: int = SHUFFLE_ROUND_COUNT) -> int:
    """The spec's function, line for line."""
    assert index < index_count
    for current_round in range(rounds):
        pivot = int.from_bytes(_hash(seed + bytes([current_round]))[0:8], "little") % index_count
        flip = (pivot + index_count - index) % index_count
        position = max(index, flip)
        source = _hash(seed + bytes([current_round]) + (position // 256).to_bytes(4, "little"))
        byte = source[(position % 256) // 8]
        bit = (byte >> (position % 8)) % 2
        index = flip if bit else index
    return index


def _swap_mirrored(out: np.ndarray, bits: np.ndarray, first: int, last: int) -> None:
    """Within out[first..last], swap position first + k with last - k for
    every k below half the stretch whose bit at last - k is set."""
    half = (last - first + 1) // 2
    if half <= 0:
        return
    low = slice(first, first + half)
    high = slice(last, last - half, -1)  # last - half >= first >= 0
    swap = bits[high].astype(bool)
    a, b = out[low].copy(), out[high].copy()
    out[low] = np.where(swap, b, a)
    out[high] = np.where(swap, a, b)


def shuffled_list(active: np.ndarray, seed: bytes, rounds: int = SHUFFLE_ROUND_COUNT) -> np.ndarray:
    """`active[compute_shuffled_index(i, n, seed)]` for every i, as int64."""
    out = np.array(active, dtype=np.int64)
    n = len(out)
    if n < 2:
        return out
    chunks = (n + 255) // 256
    for current_round in reversed(range(rounds)):
        tag = seed + bytes([current_round])
        pivot = int.from_bytes(_hash(tag)[0:8], "little") % n
        source = b"".join(_hash(tag + chunk.to_bytes(4, "little")) for chunk in range(chunks))
        # bit p of the round: byte p // 8 of the chunks' digests, its bit p % 8
        bits = np.unpackbits(np.frombuffer(source, np.uint8), bitorder="little")
        _swap_mirrored(out, bits, 0, pivot)
        _swap_mirrored(out, bits, pivot + 1, n - 1)
    return out
