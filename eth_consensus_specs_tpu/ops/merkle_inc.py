"""Incremental dirty-subtree merkleization — a device-resident tree forest.

ROADMAP item 1's standing perf debt: `run_epochs(with_root="state")`
re-merkleizes the ENTIRE state tree every epoch (r04's +1463%
`resident_epoch_plus_root_ms` advisory), even though an accounting epoch
dirties only the balance column, a handful of u64 epoch fields, and the
justification bits. The Verkle/binary-Merkle benchmarking literature
(PAPERS.md, arXiv:2504.14069) quantifies the asymmetry this module
exploits: an UPDATE should cost O(changed leaves x tree depth), not
O(tree size). The fastest hash is the one you don't recompute.

Layout — every tree keeps ALL internal levels resident in HBM as one
flat buffer, leaves first, root last::

    nodes: u32[2^(d+1)-1, 8]      level k at offset 2^(d+1) - 2^(d-k+1)

so `nodes[-1]` is the root and a parent at level k+1 sits at a shift of
its children's indices — no pointer chasing, pure index arithmetic.

Update path (:func:`path_update`): scatter the K dirty leaves, then per
level gather the 2K children, hash, scatter the K parents — ONE
fixed-shape [K, 16] compression body reused by a `fori_loop` over the
levels (dynamic offsets, static shapes), so the graph stays one sha body
+ the loop regardless of depth. Duplicate ancestors (two dirty siblings)
are rehashed redundantly rather than deduplicated: the scatter is
idempotent (same parent -> same hash) and static shapes beat a compacted
but dynamic index set.

Dirty capacity K is a COMPILE key, pow2-bucketed through
serve/buckets.inc_dirty_bucket (the serve-buckets idiom: a small set of
capacities ever compiles; `buckets.merkle_inc_key` is the LIVE key fn
jaxlint proves injective). The live dirty count is data: when it exceeds
the capacity — or the measured crossover where K x depth path work loses
to one vectorized rebuild (`buckets.inc_dense_count`) — `apply_dirty`'s
`lax.cond` takes the DENSE branch, an exact-shrinking-width rebuild of
every level. Both branches produce identical buffers for the same leaf
content; the root is bit-identical to `ops/merkle.tree_root_words` over
the same leaves on every path (tests/test_merkle_inc.py).

Mesh (the PR 8 seams): a forest shards its LEAF axis over the (dp, sp)
serve mesh — `nodes: u32[S, 2^(dl+1)-1, 8]` holds S local trees of depth
dl = d - log2(S), sharded on axis 0 via shard_map. Per-shard path
updates need NO collectives below the shard boundary (each shard owns
its subtree); the log-depth combine above it runs on the gathered
per-shard roots (`forest_root`), S-1 hashes on [S, 8] — tiny. Sharded
roots are bit-identical to the single-device forest because the level
structure is the same tree. Non-pow2 shard counts don't align with
binary tree levels, so `forest_shards` falls back to 1 for them.

Donation: the jitted kernels donate the node buffer (`donate_argnums=
(0,)`) — the forest is updated in place, never copied; jaxlint's
donation-audit PROVES the alias per kernel (the registry family
`merkle_inc` declares `donate=(0,)`, analysis/kernels.py), and rangelint
proves the hash-word/index lanes from the declared domains. The
dirty-index extraction is i32-pure on purpose (an `associative_scan`
prefix sum + drop-mode scatter instead of `jnp.nonzero`/`cumsum`, whose
i64 avals under the package x64 flag would both trip x64-drift and land
outside the range interpreter's proven primitive set).
"""

from __future__ import annotations

import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.ops.sha256 import sha256_pair_words

__all__ = [
    "apply_dirty",
    "build_forest",
    "build_levels",
    "dirty_indices",
    "forest_apply",
    "forest_root",
    "forest_shards",
    "inc_update_hashes",
    "path_update",
    "tree_depth",
    "tree_nodes",
    "update_forest_device",
]


def tree_nodes(depth: int) -> int:
    """Rows of the flat node buffer of a depth-`depth` tree."""
    return (1 << (depth + 1)) - 1


def tree_depth(n_nodes: int) -> int:
    """Inverse of :func:`tree_nodes` (n_nodes = 2^(d+1)-1)."""
    return (n_nodes + 1).bit_length() - 2


def inc_update_hashes(depth: int, cap: int, leaf_hashes: int = 0) -> int:
    """Compressions ONE sparse path update executes at capacity `cap`:
    the kernel hashes exactly cap rows per level (padding duplicates
    included — static shapes) plus `leaf_hashes` per dirty leaf to
    derive the leaf chunk itself. This is the honest work count the
    resident roofline accounting uses (capacity-based: the dispatch
    does this work whether 1 or cap leaves are really dirty)."""
    return cap * (depth + leaf_hashes)


def build_levels_hashes(depth: int) -> int:
    """Compressions build_levels executes at `depth`: every level at the
    width of the first (unlike ops/merkle.tree_root_words on a deep tree;
    no benchmark cell prices the forest builder yet)."""
    return depth << (depth - 1) if depth else 0


def build_levels(leaves: jnp.ndarray) -> jnp.ndarray:
    """u32[..., 2^d, 8] leaves -> u32[..., 2^(d+1)-1, 8] all levels,
    leaves first, root last (traceable, batched over leading dims; the
    dense-rebuild branch and the forest builder share it).

    ONE compression body for the whole tree: a ``fori_loop`` over the
    levels hashes a fixed-width [2^(d-1), 16] buffer and writes the
    result at the level's offset in the flat node buffer. Only the first
    2^(d-l) rows of level l's block are live; the tail is overwritten by
    the next level's block, which starts exactly where the live rows end
    (and the last block's tail falls in a pad that is sliced off). That
    is d*2^(d-1) compressions for a tree of 2^d-1 — d/2 times the exact
    work, milliseconds at depth 20. A compression body a level at exact
    widths does the exact work, but on an accelerator every such body is
    its own several seconds of compile, and a state forest has three
    trees of ~20 levels."""
    n = leaves.shape[-2]
    depth = n.bit_length() - 1
    if depth == 0:
        return leaves
    lead = leaves.shape[:-2]
    w = n // 2
    zero = (jnp.int32(0),) * len(lead)
    nodes = jnp.concatenate(
        [leaves, jnp.zeros((*lead, n - 1 + w, 8), leaves.dtype)], axis=-2
    )

    def level(lvl, carry):
        nodes, buf = carry
        h = sha256_pair_words(buf.reshape(-1, 16)).reshape(*lead, w, 8)
        # level l starts after levels 0..l-1: sum(n >> i) = 2n - (2n >> l)
        off = jnp.int32(2 * n) - (jnp.int32(2 * n) >> lvl.astype(jnp.int32))
        nodes = lax.dynamic_update_slice(nodes, h, (*zero, off, jnp.int32(0)))
        return nodes, jnp.concatenate([h, jnp.zeros_like(h)], axis=-2)

    # i32 loop bounds: python-int bounds widen the counter to i64 under
    # the package-wide x64 flag (the jaxlint x64-drift rule)
    nodes, _ = lax.fori_loop(
        jnp.int32(1), jnp.int32(depth + 1), level, (nodes, leaves)
    )
    return nodes[..., : 2 * n - 1, :]


_PREFIX_ROW = 1024


def _prefix_sum_i32(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of i32[n] in two levels: within rows of 1024,
    then over the row totals. Compiled for a v5e at n = 2^20 a
    log-depth ``lax.associative_scan`` takes 96 s (its strided slices
    and interleaves unroll twenty levels deep), one flat ``lax.cumsum``
    17 s, this 1 s (PERF.md, PR 22)."""
    n = x.shape[-1]
    if n % _PREFIX_ROW:
        return lax.cumsum(x, axis=0)
    rows = lax.cumsum(x.reshape(n // _PREFIX_ROW, _PREFIX_ROW), axis=1)
    totals = rows[:, -1]
    before = lax.cumsum(totals, axis=0) - totals
    return (rows + before[:, None]).reshape(n)


def dirty_indices(mask: jnp.ndarray, cap: int) -> jnp.ndarray:
    """bool[L] -> i32[cap] packed indices of the True entries
    (ascending), padded with 0. Entries past `cap` are dropped — the
    caller's crossover cond must have routed such masks to the dense
    rebuild. i32-pure: a prefix sum over i32 + drop-mode scatter (no
    `nonzero`/`jnp.cumsum` — their i64 avals under the package x64 flag
    would drift the kernel's dtype set)."""
    n = mask.shape[-1]
    pos = _prefix_sum_i32(mask.astype(jnp.int32)) - 1
    pos = jnp.where(mask, pos, jnp.int32(cap))
    return jnp.zeros(cap, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )


def path_update(nodes: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Re-hash the ancestor paths of K dirty leaves.

    nodes: u32[2^(d+1)-1, 8] flat forest tree; idx: i32[K] leaf indices
    (duplicates allowed — idempotent); vals: u32[K, 8] new leaf chunk
    words. Exactly K compressions per level through ONE [K, 16] body in
    a fori_loop with dynamic level offsets (static shapes: the graph
    never grows with depth)."""
    depth = tree_depth(nodes.shape[-2])
    if depth == 0:
        return nodes.at[jnp.zeros((), jnp.int32)].set(vals[0])
    cap2 = nodes.shape[-2] + 1  # 2^(d+1); level k offset = cap2 - (cap2 >> k)

    def level(k, carry):
        nodes, idx = carry
        parent = idx >> 1
        off_c = jnp.int32(cap2) - (jnp.int32(cap2) >> k)
        off_p = jnp.int32(cap2) - (jnp.int32(cap2) >> (k + jnp.int32(1)))
        left = nodes[off_c + 2 * parent]
        right = nodes[off_c + 2 * parent + 1]
        h = sha256_pair_words(jnp.concatenate([left, right], axis=-1))
        return nodes.at[off_p + parent].set(h), parent

    nodes = nodes.at[idx].set(vals)
    nodes, _ = lax.fori_loop(
        jnp.int32(0), jnp.int32(depth), level, (nodes, idx)
    )
    return nodes


def apply_dirty(
    nodes: jnp.ndarray, mask: jnp.ndarray, leaf_fn, cap: int, dense_count: int
) -> jnp.ndarray:
    """One tree's epoch update: sparse path rehash or dense rebuild.

    `leaf_fn(idx: i32[J]) -> u32[J, 8]` derives leaf chunk words at the
    given leaf indices (vectorized — called with the cap dirty indices
    on the sparse branch, `arange(L)` on the dense one; it must return
    the SSZ zero chunk for padding indices beyond the live leaf count).
    The `lax.cond` routes on the LIVE dirty count: <= `dense_count`
    takes the O(dirty x depth) path update, above it (capacity overflow
    or the measured crossover — serve/buckets.inc_dense_count) the
    exact-width dense rebuild. Both produce identical buffers for the
    same leaf content."""
    n_leaves = (nodes.shape[-2] + 1) // 2
    count = jnp.sum(mask.astype(jnp.int32), dtype=jnp.int32)

    def sparse(nodes):
        idx = dirty_indices(mask, cap)
        return path_update(nodes, idx, leaf_fn(idx))

    def dense(nodes):
        del nodes  # fully rebuilt from the leaf source
        return build_levels(leaf_fn(jnp.arange(n_leaves, dtype=jnp.int32)))

    return lax.cond(count > jnp.int32(dense_count), dense, sparse, nodes)


# ------------------------------------------------------------- forests --
#
# A forest tree is nodes[S, 2^(dl+1)-1, 8]: S local trees over the
# leaf-axis shards (S=1 without a mesh). The top log2(S) levels are not
# stored — they are S-1 hashes over the gathered shard roots, recomputed
# per root read (forest_root).


def forest_shards(depth: int, mesh=None) -> int:
    """Shards a depth-`depth` forest tree splits into on `mesh` — the
    mesh's device count when it is a power of two that divides the leaf
    level, else 1 (binary tree levels cannot split across a non-pow2
    grid; the single-device forest stays bit-identical)."""
    from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count

    s = shard_count(mesh)
    if s <= 1 or s & (s - 1) or (1 << depth) % s or s > (1 << depth):
        return 1
    return s


def build_forest(leaves: jnp.ndarray, shards: int) -> jnp.ndarray:
    """u32[2^d, 8] global leaves -> u32[S, 2^(dl+1)-1, 8] local trees
    (traceable; batched build_levels over the shard axis)."""
    n = leaves.shape[-2]
    return build_levels(leaves.reshape(shards, n // shards, 8))


def forest_root(nodes: jnp.ndarray) -> jnp.ndarray:
    """u32[S, M, 8] forest tree -> u32[8] root: the per-shard roots
    reduced through the log-depth top combine (S=1: the local root IS
    the tree root). Bit-identical to the unsharded tree — the top
    levels are the same tree, just not stored.

    For live MESH-sharded buffers prefer the root `forest_apply`
    returns: it is combined INSIDE the shard_map via an explicit
    all-gather, replicated on every shard, rather than leaving the
    S-way resharding of an [S, 8] array to the SPMD partitioner."""
    # static slices only (a mixed-int index like nodes[0, -1, :] lowers
    # through i64 index normalization — x64-drift in a u32 kernel)
    shard_roots = nodes[:, -1:, :].reshape(nodes.shape[0], 8)
    if nodes.shape[0] == 1:
        return shard_roots.reshape(8)
    return build_levels(shard_roots)[-1:, :].reshape(8)


def forest_apply(
    nodes: jnp.ndarray,
    mask: jnp.ndarray,
    leaf_inputs: tuple,
    leaf_fn,
    cap: int,
    dense_count: int,
    mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply one epoch's dirty set to a forest tree (traceable).

    nodes: u32[S, M, 8]; mask: bool[S, Ll] per-shard dirty leaves;
    leaf_inputs: tuple of arrays with leading [S, Ll] — the per-leaf
    source data; `leaf_fn(inputs, idx)` gets the shard-local input
    tuple (leading [Ll]) and i32[J] local indices and returns u32[J, 8]
    leaf chunk words. With a mesh the S axis shards over (dp, sp):
    per-shard path updates run without collectives (each shard owns its
    subtree and takes its OWN sparse/dense cond on its local count);
    above the shard boundary ONE log-depth all-gather hands every shard
    the S shard roots and each computes the replicated top combine —
    the only collective in the kernel. Returns (nodes, root)."""

    def local_update(nodes1, mask1, *inputs1):
        # one [1, M, 8] shard block (or the whole S=1 forest)
        fn = lambda idx: leaf_fn(tuple(a[0] for a in inputs1), idx)
        return apply_dirty(nodes1[0], mask1[0], fn, cap, dense_count)[None]

    if mesh is None or nodes.shape[0] == 1:
        assert nodes.shape[0] == 1, "multi-shard forest needs its mesh"
        nodes = local_update(nodes, mask, *leaf_inputs)
        return nodes, nodes[:, -1:, :].reshape(8)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from eth_consensus_specs_tpu.parallel.mesh_ops import BATCH_AXES

    def local(nodes1, mask1, *inputs1):
        out = local_update(nodes1, mask1, *inputs1)
        # log-depth combine above the shard boundary: every shard
        # gathers the S local roots and reduces the (tiny) top tree
        # itself — replicated output, no partitioner-driven resharding
        local_root = out[:, -1:, :].reshape(8)
        shard_roots = lax.all_gather(local_root, BATCH_AXES, tiled=False)
        return out, build_levels(shard_roots)[-1:, :].reshape(8)

    spec = P(BATCH_AXES)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec) + (spec,) * len(leaf_inputs),
        out_specs=(spec, P()),
        check_vma=False,
    )
    return fn(nodes, mask, *leaf_inputs)


# ------------------------------------------------- jitted entry points --
#
# One compiled executable per (depth, capacity, dense threshold[, mesh])
# — the capacity is the pow2 compile bucket (serve/buckets
# .inc_dirty_bucket), exactly the serve-buckets idiom. The node buffer
# is DONATED: updates are in place, jaxlint's donation-audit proves it.


@lru_cache(maxsize=None)
def _apply_kernel(depth: int, cap: int, dense_count: int):
    """Single-device identity-leaf forest update: (nodes[1, M, 8],
    mask[1, L], leaves[1, L, 8]) -> (nodes, root), leaves gathered
    straight from the provided leaf level."""

    @partial(jax.jit, donate_argnums=(0,))
    def run(nodes, mask, leaves):
        fn = lambda inputs, idx: inputs[0][idx]
        return forest_apply(
            nodes, mask, (leaves,), fn, cap, dense_count, mesh=None
        )

    return run


@lru_cache(maxsize=None)
def _apply_kernel_mesh(mesh, depth: int, cap: int, dense_count: int):
    """Mesh variant of :func:`_apply_kernel`: the shard axis of
    (nodes[S, Ml, 8], mask[S, Ll], leaves[S, Ll, 8]) splits over the
    (dp, sp) grid; capacity and crossover apply PER SHARD."""

    @partial(jax.jit, donate_argnums=(0,))
    def run(nodes, mask, leaves):
        fn = lambda inputs, idx: inputs[0][idx]
        return forest_apply(
            nodes, mask, (leaves,), fn, cap, dense_count, mesh=mesh
        )

    return run


def _clear_mesh_kernels_after_fork_in_child() -> None:
    # fork-safety: compiled executables reference the parent's devices
    _apply_kernel_mesh.cache_clear()


os.register_at_fork(after_in_child=_clear_mesh_kernels_after_fork_in_child)


def update_forest_device(
    nodes, mask, leaves, mesh=None, cap: int | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Non-traced convenience entry: one forest-tree update dispatch.

    Buckets the LIVE dirty count into a pow2 capacity
    (serve/buckets.inc_dirty_bucket), notes the compile key through the
    live `buckets.merkle_inc_key` fn (`serve.compiles` accounting — the
    resident smoke's zero-cold-compile gate rides this), and records an
    honest capacity-based work span. The resident loop does NOT go
    through here (its updates fuse into the epoch jit); tests, the
    smoke bench, and standalone callers do. Returns (nodes, root)."""
    import numpy as np

    from eth_consensus_specs_tpu.serve import buckets

    shards, n_local = mask.shape
    depth = tree_depth(nodes.shape[-2]) + (shards - 1).bit_length()
    live = int(np.asarray(jnp.sum(mask, dtype=jnp.int32)))
    if cap is None:
        cap = buckets.inc_dirty_bucket(max(live, 1))
    cap = min(cap, n_local)
    dense_count = buckets.inc_dense_count(tree_depth(nodes.shape[-2]), cap)
    key = buckets.merkle_inc_key(cap, dense_count, depth, mesh=mesh)
    if shards > 1:
        fn = _apply_kernel_mesh(mesh, depth, cap, dense_count)
    else:
        fn = _apply_kernel(depth, cap, dense_count)
    real = shards * inc_update_hashes(tree_depth(nodes.shape[-2]), cap)
    with obs.span(
        "merkle_inc.update",
        work_bytes=96 * real,
        tree_depth=depth,
        dirty=live,
        capacity=cap,
        shards=shards,
    ) as sp:
        with buckets.first_dispatch(*key):
            nodes, root = fn(nodes, mask, leaves)
        sp.result = root
    obs.count("merkle_inc.updates", 1)
    obs.count("merkle_inc.dirty_leaves", live)
    obs.count("merkle_inc.real_hashes", real)
    return nodes, root
