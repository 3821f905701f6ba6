"""Sharded SSZ tree root: local subtree reduce -> all_gather -> tiny top.

The merkle tree over N chunks splits perfectly across devices: each device
owns a contiguous 2**k-leaf subtree (that's just a range of chunks), reduces
it locally with the fused level loop (ops/merkle.py:tree_root_words), and
one all_gather of the per-device subtree roots (32 bytes each) lets every
device finish the log2(n_devices)-level top redundantly — replicated output,
no further communication. Communication total: one 32B x n_devices
all_gather over ICI per tree, regardless of tree size.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from eth_consensus_specs_tpu.ops.merkle import tree_root_words

from . import SP_AXIS


def tree_root_sharded_fn(mesh: Mesh, depth: int, axis: str = SP_AXIS):
    """Build a traceable fn: uint32[2**depth, 8] (sharded on `axis`) ->
    uint32[8] root (replicated). Requires 2**depth % mesh.shape[axis] == 0
    and mesh.shape[axis] a power of two."""
    n_shards = mesh.shape[axis]
    assert n_shards & (n_shards - 1) == 0, "shard count must be a power of two"
    top_depth = (n_shards - 1).bit_length()
    local_depth = depth - top_depth
    assert local_depth >= 0, "tree shallower than the mesh axis"

    def local(leaves):
        sub_root = tree_root_words(leaves, local_depth)  # [8]
        roots = jax.lax.all_gather(sub_root, axis)  # [n_shards, 8]
        return tree_root_words(roots, top_depth)  # replicated [8]

    return shard_map(
        local,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
        check_vma=False,
    )


def tree_root_sharded(mesh: Mesh, leaves: jnp.ndarray, depth: int) -> jnp.ndarray:
    """One-shot jitted sharded root (places `leaves` on the mesh)."""
    fn = jax.jit(
        tree_root_sharded_fn(mesh, depth),
        in_shardings=NamedSharding(mesh, P(SP_AXIS)),
        out_shardings=NamedSharding(mesh, P()),
    )
    return fn(leaves)
