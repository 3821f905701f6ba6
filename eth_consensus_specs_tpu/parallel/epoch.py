"""Sharded epoch accounting: the validator axis over the mesh, explicit SPMD.

The columnar epoch kernel (ops/state_columns.py) is embarrassingly parallel
over validators except for a handful of scalar reductions (total/attesting
balances) and one scatter-add (proposer micro-rewards). This path runs the
SAME kernel body under shard_map, swapping the two reduction primitives for
collective-backed ones:

  * sum        -> local jnp.sum + lax.psum over the mesh axes (ICI all-reduce
                  of one u64 scalar);
  * scatter_add -> each shard scatters its contributions into a dense
                  global-length vector, one psum, then slices its own block
                  (proposer targets are global indices: attester i's earliest
                  includer can live on any shard).

Explicit shard_map (not auto-partitioning with NamedSharding annotations)
is deliberate: the u64 scatter under the SPMD partitioner sends XLA's
algebraic simplifier into a non-terminating rewrite loop on the CPU backend,
and on TPU the explicit form pins exactly the collectives we want — two
psums per epoch, nothing speculative.

Validator columns shard over BOTH mesh axes flattened (dp major, sp minor):
epoch accounting wants every chip, not just the dp slice.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from eth_consensus_specs_tpu.ops.altair_epoch import (
    AltairEpochColumns,
    AltairEpochParams,
    AltairEpochResult,
    altair_epoch_accounting_impl,
)
from eth_consensus_specs_tpu.ops.state_columns import (
    EpochColumns,
    EpochParams,
    EpochResult,
    JustificationState,
    epoch_accounting_impl,
)

from . import DP_AXIS, SP_AXIS

_VALIDATOR_AXES = (DP_AXIS, SP_AXIS)


class MeshReductions:
    """psum-backed reduction primitives for the epoch kernel under shard_map."""

    def __init__(self, mesh: Mesh, axes=_VALIDATOR_AXES):
        self.axes = axes
        self.n_shards = 1
        for a in axes:
            self.n_shards *= mesh.shape[a]
        # dp-major linearized shard id, matching P((dp, sp)) block order
        self.mesh = mesh

    def _shard_id(self):
        sid = lax.axis_index(self.axes[0])
        for a in self.axes[1:]:
            sid = sid * self.mesh.shape[a] + lax.axis_index(a)
        return sid

    def _psum(self, x: jnp.ndarray) -> jnp.ndarray:
        """``lax.psum`` that the chip's compiler accepts for u64 lanes.

        XLA:TPU has no 64-bit all-reduce (compiled for a described v5e
        mesh the plain psum is refused: "Supported lowering only of Sum
        all reduce" on a u64 add). So a u64 operand crosses the mesh as
        four 16-bit limbs in u32 lanes, one fused all-reduce: a limb sum
        stays below 2^32 for up to 2^16 shards, and the limbs recombine
        in u64 with the carries the wrapping adds give — bit-identical
        to the u64 sum mod 2^64."""
        if x.dtype != jnp.uint64:
            return lax.psum(x, self.axes)
        assert self.n_shards <= 1 << 16
        limbs = tuple(
            ((x >> jnp.uint64(16 * k)) & jnp.uint64(0xFFFF)).astype(jnp.uint32)
            for k in range(4)
        )
        out = jnp.zeros_like(x)
        for k, limb in enumerate(lax.psum(limbs, self.axes)):
            out = out + (limb.astype(jnp.uint64) << jnp.uint64(16 * k))
        return out

    def sum(self, x: jnp.ndarray) -> jnp.ndarray:
        return self._psum(jnp.sum(x))

    def scatter_add(self, idx: jnp.ndarray, amounts: jnp.ndarray, local_n: int) -> jnp.ndarray:
        """Cross-shard scatter-add via one dense global-length psum.

        NOTE: this is deliberately an O(n_validators) collective — the one
        reduction in the epoch kernel that is not a 32-byte scalar. At 1M
        validators it all-reduces 8 MB per epoch (16 MB as u32 limbs, see
        :meth:`_psum`), which at ICI bandwidth
        (~100 GB/s/link) is ~0.1 ms — far below the epoch kernel's compute
        time, so the simple dense form wins until profiles say otherwise.
        The sparse alternative (ragged all_to_all of (index, amount) pairs
        bucketed by destination shard) trades that bandwidth for dynamic
        shapes XLA handles poorly; revisit only if multichip profiles show
        this psum dominating."""
        global_n = local_n * self.n_shards
        dense = (
            jnp.zeros(global_n, amounts.dtype)
            .at[jnp.clip(idx, 0, global_n - 1)]
            .add(amounts)
        )
        dense = self._psum(dense)
        start = (self._shard_id() * local_n).astype(jnp.int32)
        return lax.dynamic_slice(dense, (start,), (local_n,))


def epoch_specs():
    """(cols, just, result) PartitionSpec pytrees for shard_map."""
    vec = P(_VALIDATOR_AXES)
    rep = P()
    cols = EpochColumns(*([vec] * len(EpochColumns._fields)))
    just = JustificationState(*([rep] * len(JustificationState._fields)))
    result = EpochResult(
        balance=vec,
        effective_balance=vec,
        justification_bits=rep,
        prev_justified_epoch=rep,
        prev_justified_root=rep,
        cur_justified_epoch=rep,
        cur_justified_root=rep,
        finalized_epoch=rep,
        finalized_root=rep,
        rewards=vec,
        penalties=vec,
    )
    return cols, just, result


def sharded_epoch_fn(mesh: Mesh, params: EpochParams):
    """Traceable shard_map fn: (EpochColumns, JustificationState) ->
    EpochResult, validator columns sharded over all chips, scalars
    replicated. Global validator count must divide by the chip count."""
    cols_spec, just_spec, res_spec = epoch_specs()
    red = MeshReductions(mesh)

    def local(cols, just):
        return epoch_accounting_impl(params, cols, just, red)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(cols_spec, just_spec),
        out_specs=res_spec,
        check_vma=False,
    )


def altair_epoch_specs(with_max_effective_balance: bool = False):
    """(cols, just, result) PartitionSpec pytrees for the altair+ kernel.
    The optional electra MaxEB column shards like the other validator
    vectors when present; None (pre-electra) contributes no leaves."""
    vec = P(_VALIDATOR_AXES)
    rep = P()
    cols = AltairEpochColumns(
        **{f: vec for f in AltairEpochColumns._fields if f != "max_effective_balance"},
        max_effective_balance=vec if with_max_effective_balance else None,
    )
    just = JustificationState(*([rep] * len(JustificationState._fields)))
    result = AltairEpochResult(
        balance=vec,
        effective_balance=vec,
        inactivity_scores=vec,
        justification_bits=rep,
        prev_justified_epoch=rep,
        prev_justified_root=rep,
        cur_justified_epoch=rep,
        cur_justified_root=rep,
        finalized_epoch=rep,
        finalized_root=rep,
    )
    return cols, just, result


def sharded_altair_epoch_fn(
    mesh: Mesh, params: AltairEpochParams, with_max_effective_balance: bool = False
):
    """Altair+ flag-based epoch kernel under shard_map — same collective
    shape as the phase0 path minus the proposer scatter (flags carry no
    inclusion-proposer attribution), so it is pure psum reductions."""
    cols_spec, just_spec, res_spec = altair_epoch_specs(with_max_effective_balance)
    red = MeshReductions(mesh)

    def local(cols, just):
        return altair_epoch_accounting_impl(params, cols, just, red)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(cols_spec, just_spec),
        out_specs=res_spec,
        check_vma=False,
    )


def make_sharded_epoch_fn(mesh: Mesh, params: EpochParams):
    """Jitted sharded epoch with explicit input/output placements."""
    cols_spec, just_spec, res_spec = epoch_specs()
    to_sh = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )
    return jax.jit(
        sharded_epoch_fn(mesh, params),
        in_shardings=(to_sh(cols_spec), to_sh(just_spec)),
        out_shardings=to_sh(res_spec),
    )


def sharded_step(mesh, depth: int):
    """(epoch params, the jitted sharded step, its input shardings) over
    ``mesh`` — built from the mesh alone, so the sandbox can compile it
    for a DESCRIBED four-chip topology (scripts/tpu_compile_inventory.py
    --mesh) before a real one is paid for."""
    from eth_consensus_specs_tpu.forks import get_spec

    from .merkle import tree_root_sharded_fn

    params = AltairEpochParams.from_spec(get_spec("electra", "mainnet"))
    tree_fn = tree_root_sharded_fn(mesh, depth)
    epoch_fn = sharded_altair_epoch_fn(mesh, params, with_max_effective_balance=True)
    cols_spec, just_spec, res_spec = altair_epoch_specs(with_max_effective_balance=True)
    to_sh = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )

    def full_step(c, j, lv):
        res = epoch_fn(c, j)
        root = tree_fn(lv)
        return res, root

    in_sh = (to_sh(cols_spec), to_sh(just_spec), NamedSharding(mesh, P(SP_AXIS)))
    stepped = jax.jit(
        full_step,
        in_shardings=in_sh,
        out_shardings=(to_sh(res_spec), NamedSharding(mesh, P())),
    )
    return params, stepped, in_sh
