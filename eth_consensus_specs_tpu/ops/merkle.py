"""Whole-subtree SSZ merkleization on device — ONE dispatch per tree.

A tree's hashes take milliseconds on the device, so the fixed cost of a
dispatch is what a level-per-call reduction would pay ~35 times over (not
measured on the chip for today's code). So the whole binary reduction runs
as a single jitted call: a `lax.fori_loop` over the levels carrying a
fixed-width node buffer (see tree_root_words — d/2 times the exact
tree's work, bought for a 35x drop in dispatch count and ONE compression
body in the graph; rounds unrolled on TPU, see ops/sha256.py).

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


def tree_real_hashes(depth: int) -> int:
    """Compressions tree_root_words actually executes at `depth` — the
    honest work count for bench roofline/throughput accounting: every
    level hashes the fixed 2^(d-1)-row buffer."""
    return depth << (depth - 1) if depth else 0


def tree_root_words(leaves: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Traceable tree reduction: uint32[2**depth, 8] -> uint32[8] root.

    ONE compression body: a ``fori_loop`` over the levels hashes a
    fixed-width [2^(d-1), 16] buffer whose live rows halve each level
    (the spent tail hashes garbage that never reaches a live node). That
    is d*2^(d-1) compressions for a tree of 2^d - 1: d/2 times the exact
    work, ~10x at depth 20, still milliseconds there. Unrolling the
    widest levels at exact widths buys that work back (six levels: 1.09x
    exact at depth 20), but every unrolled level is one more compression
    body for the chip's compiler, 5 to 10 s each compiled for a v5e, in
    EVERY program that roots a deep tree — the full state root has four
    such trees, ~190 s of cold start at six levels (PERF.md, PR 22). What
    the extra hashing costs at run time is not measured on the chip; the
    benchmark has to show it before a level is unrolled.

    Plain function so it composes under outer jits / shard_map (the
    sharded tree in parallel/merkle.py reduces local subtrees with this,
    then all-gathers the per-device roots)."""
    if depth == 0:
        return leaves[0]
    w = leaves.shape[0] // 2

    def level(_, b):
        h = sha256_pair_words(b.reshape(w, 16))
        return jnp.concatenate([h, jnp.zeros_like(h)], axis=0)

    # i32 loop bounds: python-int bounds widen the counter to i64 under
    # the package-wide x64 flag — the jaxlint x64-drift rule keeps this
    # kernel's jaxpr pure 32-bit
    return lax.fori_loop(jnp.int32(0), jnp.int32(depth), level, leaves)[0]


_tree_root_fused = partial(jax.jit, static_argnums=(1,))(tree_root_words)


def many_tree_root_words(leaves: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Batched tree reduction: uint32[B, 2**depth, 8] -> uint32[B, 8]
    roots, ONE dispatch for B independent subtrees (the serving layer's
    bucket-padded flush shape — compiles once per (B, depth))."""
    return jax.vmap(lambda level: tree_root_words(level, depth))(leaves)


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
