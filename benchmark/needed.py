"""What the ALGORITHM needs for one post-epoch state root, from shapes alone:
the count a roofline divides by. It does not follow what an implementation
executes (`ops/merkle.tree_real_hashes` counts d * 2**(d-1) a tree since the
level loop of PR 22): a program that hashes more must not read as closer to
its roofline."""

from __future__ import annotations


def tree_hashes(leaves: int) -> int:
    """Compressions of a binary tree over `leaves` leaves padded to a power
    of two, zero subtrees taken from the table: one a node with a non-zero
    child, level by level."""
    total, width = 0, leaves
    while width > 1:
        width = (width + 1) // 2
        total += width
    return total


def list_fold_hashes(chunks: int, limit_log2: int) -> int:
    """Zero-hash folds from the subtree's depth to the list limit's, plus the
    length mix-in."""
    depth = max(chunks - 1, 0).bit_length()
    return max(limit_log2 - depth, 0) + 1


def state_root_needed_hashes(n: int, top_fields: int = 24) -> int:
    """Altair BeaconState with n validators, static nodes given: 3 hashes a
    validator, the registry tree, the balances and inactivity-score trees
    (4 u64 a chunk), the previous-participation tree (32 flags a chunk), the
    folds to the limits (2**40 entries), three checkpoints, the top tree."""
    u64_chunks, u8_chunks = -(-n // 4), -(-n // 32)
    return (
        3 * n
        + tree_hashes(n) + list_fold_hashes(n, 40)
        + 2 * (tree_hashes(u64_chunks) + list_fold_hashes(u64_chunks, 38))
        + tree_hashes(u8_chunks) + list_fold_hashes(u8_chunks, 35)
        + 3
        + tree_hashes(top_fields)
    )


def state_root_least_bytes(n: int, top_fields: int = 24) -> int:
    """Least bytes between HBM and the cores for one root: each input read
    once (three static 32-byte rows and three u64 columns a validator, one
    participation byte, the static top chunks) and the root written. Interior
    nodes need never leave the chip."""
    return n * (3 * 32 + 3 * 8 + 1) + 32 * top_fields + 32
