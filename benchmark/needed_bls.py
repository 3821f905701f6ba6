"""What the ALGORITHM needs for a block's committee sums, from shapes alone
(as benchmark/needed.py counts a state root): the count a roofline divides
by, whatever implements the sums."""

from __future__ import annotations


def committee_sums_least_bytes(aggregates: int, committee_size: int) -> int:
    """Least bytes between HBM and the cores: each signer's affine public
    key read once (two 48-byte coordinates) and each aggregate's sum
    written as one. Partial sums need never leave the chip."""
    return aggregates * committee_size * 96 + aggregates * 96
