"""Multi-chip sharding correctness in the test suite: the shard_map epoch
kernels and the sharded SSZ tree root must be bit-exact with their
single-device counterparts over the 8-virtual-device CPU mesh that
conftest.py forces (the same mesh shape the driver dry-runs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# 8-virtual-device mesh compiles — nightly lane (make test-full)
pytestmark = pytest.mark.slow
from jax.sharding import NamedSharding, PartitionSpec as P

from eth_consensus_specs_tpu.forks import get_spec
from eth_consensus_specs_tpu.ops.altair_epoch import (
    AltairEpochParams,
    altair_epoch_accounting,
)
from eth_consensus_specs_tpu.ops.merkle import _tree_root_fused
from eth_consensus_specs_tpu.ops.state_columns import EpochParams, epoch_accounting
from eth_consensus_specs_tpu.parallel import DP_AXIS, SP_AXIS, make_mesh
from eth_consensus_specs_tpu.parallel.epoch import (
    altair_epoch_specs,
    epoch_specs,
    sharded_altair_epoch_fn,
    sharded_epoch_fn,
)
from eth_consensus_specs_tpu.parallel.merkle import tree_root_sharded_fn

N_DEVICES = 8


def _mesh():
    if len(jax.devices()) < N_DEVICES:
        pytest.skip(f"needs {N_DEVICES} devices (conftest forces them on CPU)")
    return make_mesh(N_DEVICES)


def _to_shardings(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )


def test_make_mesh_shape():
    mesh = _mesh()
    assert mesh.shape[DP_AXIS] * mesh.shape[SP_AXIS] == N_DEVICES
    assert mesh.shape[SP_AXIS] == 2  # even device count -> sp=2


def test_sharded_phase0_epoch_bit_exact():
    import __graft_entry__ as g

    mesh = _mesh()
    spec = get_spec("phase0", "mainnet")
    params = EpochParams.from_spec(spec)
    cols, just = g._example_inputs(64 * N_DEVICES)
    cols_spec, just_spec, res_spec = epoch_specs()
    fn = jax.jit(
        sharded_epoch_fn(mesh, params),
        in_shardings=(_to_shardings(mesh, cols_spec), _to_shardings(mesh, just_spec)),
        out_shardings=_to_shardings(mesh, res_spec),
    )
    res = fn(cols, just)
    ref = epoch_accounting(params, cols, just)
    for name in ("balance", "effective_balance", "rewards", "penalties"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res, name)), np.asarray(getattr(ref, name)), err_msg=name
        )
    assert int(res.finalized_epoch) == int(ref.finalized_epoch)


def test_sharded_altair_epoch_bit_exact():
    import __graft_entry__ as g

    mesh = _mesh()
    spec = get_spec("deneb", "mainnet")
    params = AltairEpochParams.from_spec(spec)
    cols, just = g._example_altair_inputs(64 * N_DEVICES)
    cols_spec, just_spec, res_spec = altair_epoch_specs()
    fn = jax.jit(
        sharded_altair_epoch_fn(mesh, params),
        in_shardings=(_to_shardings(mesh, cols_spec), _to_shardings(mesh, just_spec)),
        out_shardings=_to_shardings(mesh, res_spec),
    )
    res = fn(cols, just)
    ref = altair_epoch_accounting(params, cols, just)
    for name in ("balance", "effective_balance", "inactivity_scores"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res, name)), np.asarray(getattr(ref, name)), err_msg=name
        )
    np.testing.assert_array_equal(
        np.asarray(res.justification_bits), np.asarray(ref.justification_bits)
    )


@pytest.mark.parametrize("depth", [12, 16])  # 16: local subtrees wider than a tile
def test_sharded_tree_root_matches_fused(depth):
    mesh = _mesh()
    rng = np.random.default_rng(3)
    leaves = jnp.asarray(
        rng.integers(0, 2**32, (1 << depth, 8), dtype=np.uint64).astype(np.uint32)
    )
    fn = jax.jit(
        tree_root_sharded_fn(mesh, depth),
        in_shardings=NamedSharding(mesh, P(SP_AXIS)),
        out_shardings=NamedSharding(mesh, P()),
    )
    root = fn(leaves)
    ref = _tree_root_fused(leaves, depth)
    np.testing.assert_array_equal(np.asarray(root), np.asarray(ref))


def test_sharded_epoch_scatter_add_proposer_rewards_cross_shard():
    """Proposer micro-rewards target global indices that can live on any
    shard — pin a case where every proposer index lands on shard 0."""
    import __graft_entry__ as g

    mesh = _mesh()
    spec = get_spec("phase0", "mainnet")
    params = EpochParams.from_spec(spec)
    n = 64 * N_DEVICES
    cols, just = g._example_inputs(n)
    cols = cols._replace(incl_proposer=np.zeros(n, np.int64))  # all on shard 0
    cols_spec, just_spec, res_spec = epoch_specs()
    fn = jax.jit(
        sharded_epoch_fn(mesh, params),
        in_shardings=(_to_shardings(mesh, cols_spec), _to_shardings(mesh, just_spec)),
        out_shardings=_to_shardings(mesh, res_spec),
    )
    res = fn(cols, just)
    ref = epoch_accounting(params, cols, just)
    np.testing.assert_array_equal(np.asarray(res.balance), np.asarray(ref.balance))


def test_sharded_block_slot_bit_exact():
    """One slot of the block plane (attestation scatters, sync rewards,
    deposits, withdrawal sweep) over the mesh == the unsharded kernel.
    Committee indices span every shard, so this exercises the global
    scatter path the SPMD partitioner must communicate for."""
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.ops import block_epoch as bek
    from eth_consensus_specs_tpu.parallel.block import make_sharded_block_slot_fn

    mesh = _mesh()
    spec = get_spec("deneb", "mainnet")
    n = 64 * N_DEVICES
    cols, st0, static = bek.synthetic_block_columns(spec, n, seed=5, atts_per_slot=4)
    params = bek.BlockEpochParams.from_spec(spec)
    slot_blk = jax.tree_util.tree_map(lambda a: a[0], cols)  # first slot

    fn = make_sharded_block_slot_fn(mesh, params, n)
    out = fn(
        st0,
        slot_blk,
        static.base_reward,
        static.eff_balance,
        static.withdrawable_epoch,
        static.has_eth1_cred,
        static.epoch,
        static.part_reward,
        static.prop_reward,
    )
    ref = bek.process_slot_columnar(
        params,
        n,
        st0,
        slot_blk,
        static.base_reward,
        static.eff_balance,
        static.withdrawable_epoch,
        static.has_eth1_cred,
        static.epoch,
        static.part_reward,
        static.prop_reward,
    )
    np.testing.assert_array_equal(np.asarray(out.balance), np.asarray(ref.balance))
    np.testing.assert_array_equal(np.asarray(out.cur_part), np.asarray(ref.cur_part))
    np.testing.assert_array_equal(np.asarray(out.prev_part), np.asarray(ref.prev_part))
    assert int(out.next_wd_index) == int(ref.next_wd_index)
    assert int(out.next_wd_validator) == int(ref.next_wd_validator)
