"""Whole-subtree SSZ merkleization on device — ONE dispatch per tree.

The whole binary reduction runs as a single jitted call: a
`lax.fori_loop` over the levels of an in-place node buffer with ONE
compression body in the graph (rounds unrolled on TPU, see
ops/sha256.py), where a level-per-call reduction would pay a dispatch,
and an unrolled one a several-second compile, at every level. A deep
tree's hashes are no small matter on the chip: hashed at the first
level's width every level, the three deep trees of a 2^20 state root
took 677 ms of its 850 on one v5e (ledger, PR 25). So a tree wider than
a tile hashes each level at its live width, tile by tile (see
tree_root_words: 1.04x the exact tree's work at depth 20, not d/2 x).

Hot state lives device-resident between calls (ops/state_columns.py); the
host-chunk entry below is for one-shot roots.

Virtual padding: SSZ pads leaf data with zero chunks up to the limit; a
subtree of zero chunks hashes to zerohashes[d], so padding the real leaf
count to 2**depth with zero chunks on device gives bit-identical roots
(cf. reference utils/merkle_minimal.py:47-91). Live nodes stay at the
front of the buffer every level, so the tail garbage (hashes of spent
positions) never reaches them.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.obs import watchdog, xprof

from .sha256 import sha256_pair_words


# Rows a tile of the level loop hashes (tree_root_words). A power of two,
# at least 128 so that the tile's sha keeps its unrolled rounds
# (sha256.SMALL_BATCH). PERF.md section 6, PR 26, has the sweep on one v5e.
TILE_ROWS = 1 << 12


def tree_real_hashes(depth: int, tile_rows: int = TILE_ROWS) -> int:
    """Compressions tree_root_words executes at `depth`: what the span's
    work count and `state_root.real_hashes` report (what the algorithm
    NEEDS is benchmark/needed.py's count). A tree of at most `tile_rows`
    pairs hashes its first level's width at every level, d * 2^(d-1); a
    wider one hashes each level's live width, never less than one tile."""
    if depth == 0:
        return 0
    w = 1 << (depth - 1)
    if w <= tile_rows:
        return depth * w
    return sum(max(w >> level, tile_rows) for level in range(depth))


def tree_root_words(
    leaves: jnp.ndarray, depth: int, tile_rows: int = TILE_ROWS
) -> jnp.ndarray:
    """Traceable tree reduction: uint32[2**depth, 8] -> uint32[8] root.

    ONE compression body in a ``fori_loop`` over the levels of an
    in-place [2^d, 8] node buffer whose live rows halve each level and
    stay at its front (the spent tail holds garbage that never reaches a
    live node). Which loop is chosen from the shape alone:

    * at most `tile_rows` pairs at the first level: every level hashes
      the whole [2^(d-1), 16] buffer, d * 2^(d-1) compressions for a tree
      of 2^d - 1;
    * wider: level l hashes its live 2^(d-1-l) pairs in tiles of
      `tile_rows`, one tile where fewer are live. Tile j reads rows
      [2jT, 2jT + 2T) and writes rows [jT, jT + T); every earlier tile
      wrote below row jT, so in place is safe. 1.04x the exact tree at
      depth 20 where the whole-width loop is 10x.

    On one v5e the whole-width loop cost the 2^20 state root 677 of its
    850 ms (511 ms the registry tree, 83 ms each 2^18-chunk tree; ledger,
    PR 25); the tile loop takes 21.6 and 6.1 ms there, a tile's rows
    and its sha staying in the chip's fast memory (PERF.md, PR 26).
    Unrolling levels at exact widths would buy the same work back at
    one more compression body a level for the chip's compiler, 5 to
    10 s each compiled for a v5e, in EVERY program that roots a deep
    tree (PERF.md, PR 22); the tile loop keeps the one body.

    Plain function so it composes under outer jits / vmap / shard_map
    (the trip counts depend on the level alone; the sharded tree in
    parallel/merkle.py reduces local subtrees with this, then
    all-gathers the per-device roots)."""
    if depth == 0:
        return leaves[0]
    w = leaves.shape[0] // 2
    # i32 loop bounds and indices: python-int bounds widen the counter to
    # i64 under the package-wide x64 flag — the jaxlint x64-drift rule
    # keeps this kernel's jaxpr pure 32-bit
    zero = jnp.int32(0)
    if w <= tile_rows:

        def level(_, b):
            h = sha256_pair_words(b.reshape(w, 16))
            return jnp.concatenate([h, jnp.zeros_like(h)], axis=0)

    else:
        t = jnp.int32(tile_rows)

        def tile(j, b):
            pairs = lax.dynamic_slice(b, (2 * j * t, zero), (2 * tile_rows, 8))
            h = sha256_pair_words(pairs.reshape(tile_rows, 16))
            return lax.dynamic_update_slice(b, h, (j * t, zero))

        def level(lvl, b):
            tiles = jnp.maximum((jnp.int32(w) >> lvl) // t, jnp.int32(1))
            return lax.fori_loop(zero, tiles, tile, b)

    return lax.fori_loop(zero, jnp.int32(depth), level, leaves)[0]


_tree_root_fused = partial(jax.jit, static_argnums=(1,))(tree_root_words)


def many_tree_root_words(
    leaves: jnp.ndarray, depth: int, tile_rows: int = TILE_ROWS
) -> jnp.ndarray:
    """Batched tree reduction: uint32[B, 2**depth, 8] -> uint32[B, 8]
    roots, ONE dispatch for B independent subtrees (the serving layer's
    bucket-padded flush shape — compiles once per (B, depth))."""
    return jax.vmap(lambda level: tree_root_words(level, depth, tile_rows))(leaves)


_many_tree_root_fused = partial(jax.jit, static_argnums=(1,))(many_tree_root_words)


# -- mesh-sharded multi-tree dispatch: the batch (tree) axis splits over
# the serve mesh; every tree is independent, so there are NO collectives
# and the per-tree roots are trivially byte-identical to the vmapped
# single-device kernel. One jitted shard_map per (mesh, depth), the jit
# cache dedupes per batch shape.
_SHARDED_MANY: dict[tuple, object] = {}


def _many_tree_root_sharded(mesh, depth: int):
    key = (mesh, depth)
    fn = _SHARDED_MANY.get(key)
    if fn is not None:
        return fn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from eth_consensus_specs_tpu.parallel.mesh_ops import BATCH_AXES

    spec = P(BATCH_AXES)
    fn = jax.jit(
        shard_map(
            lambda words: many_tree_root_words(words, depth),
            mesh=mesh,
            in_specs=spec,
            out_specs=spec,
            check_vma=False,
        )
    )
    _SHARDED_MANY[key] = fn
    return fn


def _clear_sharded_after_fork_in_child() -> None:
    # fork-safety: compiled executables reference the parent's devices
    _SHARDED_MANY.clear()


os.register_at_fork(after_in_child=_clear_sharded_after_fork_in_child)


def _chunks_to_words(chunks: np.ndarray, cap: int) -> np.ndarray:
    """uint8[N, 32] chunks (or pre-packed uint32[N, 8] BE words) ->
    uint32[cap, 8], zero-padded. Exposed so the service's host-prep
    stage can pack off the dispatch thread."""
    if chunks.dtype == np.uint32:
        words = np.ascontiguousarray(chunks)
    else:
        n = chunks.shape[0]
        words = np.ascontiguousarray(chunks).view(">u4").astype(np.uint32).reshape(n, 8)
    n = words.shape[0]
    assert n <= cap
    if n < cap:
        words = np.concatenate([words, np.zeros((cap - n, 8), dtype=np.uint32)], axis=0)
    return words


def merkleize_many_device(
    trees: list[np.ndarray], depth: int, pad_batch: int | None = None, mesh=None
) -> list[bytes]:
    """Merkleize many independent subtrees of one depth in a single
    dispatch. Each entry is uint8[N_i, 32] chunks (N_i <= 2**depth) or a
    pre-packed uint32[N_i, 8] word array; the batch dimension is padded
    with all-zero trees up to `pad_batch` so the compiled executable is
    shared across every flush in the same bucket. With a multi-device
    `mesh` the tree axis shards over it (pad_batch then rounds up to a
    multiple of the shard count — serve/buckets.py's mesh-aware buckets
    already are). Roots are bit-identical to per-tree
    `merkleize_subtree_device` (same kernel, vmapped) on every path."""
    from eth_consensus_specs_tpu.parallel.mesh_ops import (
        mesh_signature,
        pad_to_shards,
        shard_count,
    )

    b = len(trees)
    cap = 1 << depth
    shards = shard_count(mesh)
    if shards <= 1:
        mesh = None
    batch = pad_batch or b
    if mesh is not None:
        batch = pad_to_shards(batch, shards)
    assert b <= batch
    words = np.zeros((batch, cap, 8), np.uint32)
    for i, chunks in enumerate(trees):
        words[i] = _chunks_to_words(chunks, cap)
    real = batch * tree_real_hashes(depth)
    with obs.span(
        "merkle.many_subtree_root",
        work_bytes=96 * real,
        tree_depth=depth,
        trees=b,
        padded_trees=batch,
        mesh=mesh_signature(mesh),
        mesh_shards=shards,
        per_shard_trees=batch // shards,
    ) as sp:
        if mesh is not None:
            obs.count("mesh.dispatches", 1)
            obs.count("mesh.sharded_items", b)
            fn = _many_tree_root_sharded(mesh, depth)
            sp.result = roots = np.asarray(fn(jnp.asarray(words)))
        else:
            sp.result = roots = np.asarray(
                _many_tree_root_fused(jnp.asarray(words), depth)
            )
    obs.count("merkle.trees", b)
    obs.count("merkle.real_hashes", real)
    if xprof.enabled():
        # once per (batch, depth[, mesh shape]): what XLA compiled for
        # this bucket vs the 96 B × real-hash floor the span's roofline
        # was judged on — sharded shapes attribute per (op, mesh-shape)
        if mesh is not None:
            xprof.analyze(
                "merkle_many",
                _many_tree_root_sharded(mesh, depth),
                (jax.ShapeDtypeStruct((batch, cap, 8), jnp.uint32),),
                hand_bytes=96 * real,
                dims=(batch, depth, *(int(mesh.shape[a]) for a in mesh.axis_names)),
            )
        else:
            xprof.analyze(
                "merkle_many",
                _many_tree_root_fused,
                (jax.ShapeDtypeStruct((batch, cap, 8), jnp.uint32), depth),
                hand_bytes=96 * real,
                dims=(batch, depth),
            )
    out = [roots[i].astype(">u4", order="C").view(np.uint8).tobytes() for i in range(b)]
    if b and watchdog.should_check("merkle"):
        i = watchdog.call_salt("merkle") % b
        watchdog.check_merkle_root(words[i], depth, out[i])
    return out


def merkleize_subtree_device(chunks: np.ndarray, depth: int) -> bytes:
    """Merkleize uint8[N, 32] chunks into the root of a depth-`depth` subtree.

    N must satisfy N <= 2**depth; zero-chunk padding to 2**depth happens
    host-side. One compiled shape per depth (persistently cached, see
    utils/cache.py).
    """
    n = chunks.shape[0]
    cap = 1 << depth
    assert n <= cap
    words = np.ascontiguousarray(chunks).view(">u4").astype(np.uint32).reshape(n, 8)
    if n < cap:
        words = np.concatenate([words, np.zeros((cap - n, 8), dtype=np.uint32)], axis=0)
    real = tree_real_hashes(depth)
    with obs.span(
        "merkle.subtree_root", work_bytes=96 * real, tree_depth=depth, leaf_chunks=n
    ) as sp:
        sp.result = root_words = np.asarray(_tree_root_fused(jnp.asarray(words), depth))
    obs.count("merkle.trees", 1)
    obs.count("merkle.real_hashes", real)
    obs.count("merkle.leaf_chunks", n)
    if xprof.enabled():
        xprof.analyze(
            "merkle",
            _tree_root_fused,
            (jax.ShapeDtypeStruct((cap, 8), jnp.uint32), depth),
            hand_bytes=96 * real,
            dims=(depth,),
        )
    root = root_words.astype(">u4", order="C").view(np.uint8).tobytes()
    if watchdog.should_check("merkle"):
        watchdog.check_merkle_root(words, depth, root)
    return root


# Device/host crossover: ONE cost model shared with the serving layer's
# bucket planner (serve/buckets.py is the home; re-exported here so ops
# callers keep their import path and the two can never disagree).
from eth_consensus_specs_tpu.serve.buckets import (  # noqa: E402
    DEVICE_SUBTREE_THRESHOLD,
    device_subtree_worthwhile,
)

__all__ = [
    "DEVICE_SUBTREE_THRESHOLD",
    "device_subtree_worthwhile",
    "merkleize_many_device",
    "merkleize_subtree_device",
    "many_tree_root_words",
    "tree_real_hashes",
    "tree_root_words",
]
