"""ops/merkle.tree_root_words on both sides of its tile boundary: a tree of
at most `tile_rows` pairs hashes every level at the first level's width, a
wider one hashes each level's live rows in tiles. Roots against hashlib,
the executed-hash count against a re-count of the loop's own tiles. CPU:
the round-scan sha, so these check answers and counts, never speed.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import needed
from eth_consensus_specs_tpu.obs.watchdog import host_tree_root_words as _root_hashlib
from eth_consensus_specs_tpu.ops.merkle import (
    TILE_ROWS,
    many_tree_root_words,
    tree_real_hashes,
    tree_root_words,
)
from eth_consensus_specs_tpu.ssz.merkle import zerohashes


def _as_bytes(root_words) -> bytes:
    return np.asarray(root_words).astype(">u4").tobytes()


def _leaves(depth: int, kind: str, batch: int | None = None) -> np.ndarray:
    shape = (1 << depth, 8) if batch is None else (batch, 1 << depth, 8)
    if kind == "zero":
        return np.zeros(shape, np.uint32)
    rng = np.random.default_rng(depth)
    return rng.integers(0, 2**32, size=shape, dtype=np.uint64).astype(np.uint32)


# first-level pairs 2^(d-1) against the tile: below it, equal to it (both the
# whole-width loop), twice it, and eight and thirty-two times it (the tile
# loop, levels of several tiles and levels narrower than one)
BOUNDARY = [(4, 16), (5, 16), (6, 16), (6, 4), (8, 4), (3, 1), (1, 1)]


@pytest.mark.parametrize("kind", ["random", "zero"])
@pytest.mark.parametrize("depth,tile", BOUNDARY)
def test_tree_root_matches_hashlib_across_the_tile_boundary(depth, tile, kind):
    leaves = _leaves(depth, kind)
    got = _as_bytes(tree_root_words(jnp.asarray(leaves), depth, tile_rows=tile))
    assert got == _root_hashlib(leaves)
    if kind == "zero":
        assert got == zerohashes[depth]


@pytest.mark.parametrize("kind", ["random", "zero"])
@pytest.mark.parametrize("depth,tile", BOUNDARY)
def test_many_tree_roots_match_hashlib_across_the_tile_boundary(depth, tile, kind):
    leaves = _leaves(depth, kind, batch=3)
    roots = many_tree_root_words(jnp.asarray(leaves), depth, tile_rows=tile)
    assert [_as_bytes(r) for r in roots] == [_root_hashlib(t) for t in leaves]


def test_tree_root_at_the_committed_tile_two_levels_past_it():
    depth = TILE_ROWS.bit_length() + 2  # 4 tiles, 2 tiles, then 1 a level
    leaves = _leaves(depth, "random")
    assert _as_bytes(tree_root_words(jnp.asarray(leaves), depth)) == _root_hashlib(leaves)


def _recount(depth: int, tile: int) -> int:
    """Rows the loop hashes, tile by tile, as tree_root_words walks them."""
    w = (1 << depth) // 2
    total = 0
    for level in range(depth):
        if w <= tile:  # the whole width, every level
            total += w
        else:
            total += sum(tile for _ in range(max((w >> level) // tile, 1)))
    return total


@pytest.mark.parametrize("depth", range(22))
def test_tree_real_hashes_counts_the_loops_own_tiles(depth):
    assert tree_real_hashes(depth) == _recount(depth, TILE_ROWS)
    assert tree_real_hashes(depth, 4) == _recount(depth, 4)
    assert tree_real_hashes(depth) >= needed.tree_hashes(1 << depth)


def test_tree_real_hashes_near_needed_at_depth_20_and_pinned_at_3():
    assert tree_real_hashes(20) / needed.tree_hashes(2**20) < 1.2
    assert tree_real_hashes(3) == 12
    assert TILE_ROWS >= 128 and TILE_ROWS & (TILE_ROWS - 1) == 0
