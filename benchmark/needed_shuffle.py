"""What the ALGORITHM needs for an epoch's shuffled committee list, from
shapes alone (as benchmark/needed.py counts a state root): the count a
roofline divides by, whatever implements the rounds."""

from __future__ import annotations

SHUFFLE_ROUND_COUNT = 90


def shuffle_least_bytes(validators: int) -> int:
    """Least bytes between HBM and the cores: each active index read once
    and each entry of the list written once (4 bytes each way a
    validator), the 32-byte seed and the rounds' 4-byte pivots read. The
    decision digests, the rounds' intermediate lists and the bits need
    never leave the chip."""
    return validators * (4 + 4) + 32 + 4 * SHUFFLE_ROUND_COUNT
