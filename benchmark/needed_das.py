"""What the ALGORITHM needs for a block's data column sidecars, from shapes
alone (as benchmark/needed.py counts a state root): the counts a roofline
divides by, whatever implements the sums and the transforms."""

from __future__ import annotations

FIELD_ELEMENTS_PER_CELL = 64


def proof_sums_least_bytes(columns: int, blobs: int) -> int:
    """Least bytes between HBM and the cores for the per-sidecar proof sums:
    two sums a sidecar (r^k and r^k h^64 weights), each reading every proof
    of the sidecar once as an affine point (two 48-byte coordinates) with
    its 32-byte scalar and writing one point. Doublings, additions and
    partial sums need never leave the chip."""
    items = 2 * columns
    return items * (blobs * (96 + 32) + 96)


def cell_interpolation_least_bytes(cells: int) -> int:
    """Least bytes for the cells' inverse FFTs: each 32-byte field element
    read once and its coefficient written once. Stages stay on the chip."""
    return cells * FIELD_ELEMENTS_PER_CELL * (32 + 32)
