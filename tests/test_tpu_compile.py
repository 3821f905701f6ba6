"""The served path's kernels, compiled for a DESCRIBED v5e at the widths a
mainnet slot dispatches them — the part of the compile inventory
(scripts/tpu_compile_inventory.py, analysis/chip_programs.py) that takes
seconds, kept as tests so that every later PR is held to "the chip's
compiler accepts this" at no chip time. No device is attached and nothing
runs: a pass says the program lowers for the TPU (64-bit limb lanes, the
unrolled sha rounds, the forest's dynamic level offsets) and fits its
memory, nothing about answers or speed.

The topology is described inside a module-scoped fixture, which skips
where it cannot be; only the xdist worker that is handed this file loads
the chip's library, and the programs compile in the test's own process.
Keep these tests in this one file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from eth_consensus_specs_tpu.analysis import chip_programs

HBM_BYTES = 16 << 30  # one v5e chip (Google Cloud documentation, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Two caches are kept out of these tests, both ways. A compile for a
    described chip is written to the persistent cache but cannot be read
    back without one: it is off in here. And JAX caches a function's TRACE
    by its argument shapes, not by which branch the kernel took when it
    asked for the backend (``chip_programs.as_accelerator``): a shape an
    earlier test of this worker traced on the CPU would compile its CPU
    graph here, and a shape traced here would hand a later CPU test the
    unrolled accelerator graph, minutes of XLA:CPU compile. So every
    trace is dropped before the first test and after the last."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _program(name: str) -> chip_programs.Program:
    return next(
        p for p in chip_programs.slot_programs(*chip_smoke.MAINNET) if p.name == name
    )


def _check_row(row: dict) -> None:
    assert row["code_bytes"] > 0
    on_device = row["argument_bytes"] + row["output_bytes"] + row["temp_bytes"]
    assert on_device - row["alias_bytes"] < HBM_BYTES, row


@pytest.mark.parametrize(
    "name", ["sha256:tile65536", "sha256:tile2048", "merkle_many", "resident_root", "fr_fft"]
)
def test_slot_program_compiles_for_one_v5e(one_chip, no_compile_cache, name):
    """Programs of the mainnet slot that compile in seconds, at exactly the
    shapes chip_smoke.py dispatches (the same list the inventory walks)."""
    _check_row(chip_programs.compile_for(one_chip, _program(name)))


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def test_forest_dense_build_at_2_20_is_one_compression_body(one_chip, no_compile_cache):
    """The resident forest's dense (re)build over 2^20 leaves: ONE sha body
    in a loop over the levels — a body a level was twenty several-second
    compiles a tree, three trees a forest."""
    from eth_consensus_specs_tpu.ops import merkle_inc

    n = chip_smoke.MAINNET.validators
    prog = chip_programs.Program(
        "forest_dense_build",
        lambda: (jax.jit(merkle_inc.build_levels), (_u32((1, n, 8), None),)),
    )
    row = chip_programs.compile_for(one_chip, prog)
    _check_row(row)
    # all levels out: 2^21 - 1 nodes of 32 B
    assert row["output_bytes"] >= (2 * n - 1) * 32


def test_tree_root_at_depth_20_compiles_with_its_tile_loop(one_chip, no_compile_cache):
    """The registry tree of a 2^20 state root: the level loop with the tile
    loop inside it, dynamic row offsets into the in-place node buffer, one
    unrolled sha body of merkle.TILE_ROWS rows."""
    from eth_consensus_specs_tpu.ops import merkle

    depth = 20
    assert (1 << (depth - 1)) > merkle.TILE_ROWS
    prog = chip_programs.Program(
        "tree_root_2_20",
        lambda: (
            jax.jit(lambda leaves: merkle.tree_root_words(leaves, depth)),
            (_u32((1 << depth, 8), None),),
        ),
    )
    _check_row(chip_programs.compile_for(one_chip, prog))


def test_forest_path_update_at_2_20_compiles(one_chip, no_compile_cache):
    """The incremental re-root's sparse leg at the registry's depth and the
    forest plan's dirty capacity: gather, one [cap, 16] sha body, scatter."""
    from eth_consensus_specs_tpu.analysis.chip_programs import slot_world_shapes
    from eth_consensus_specs_tpu.ops import merkle_inc

    n = chip_smoke.MAINNET.validators
    plan = slot_world_shapes(n)[3]
    cap = int(plan.cap_val)
    args = (
        _u32((2 * n - 1, 8), None),
        jax.ShapeDtypeStruct((cap,), jnp.int32),
        _u32((cap, 8), None),
    )
    prog = chip_programs.Program(
        "forest_path_update", lambda: (jax.jit(merkle_inc.path_update), args)
    )
    _check_row(chip_programs.compile_for(one_chip, prog))


@pytest.fixture(scope="module")
def four_chip_programs(topo):
    """``chip_smoke.py --chips 4``'s two programs over a (dp, sp) mesh of
    the four described chips; the epoch step at a registry small enough
    to compile in half a minute (what the chip's compiler refuses in it
    does not depend on the size)."""
    progs = chip_programs.mesh_programs(
        topo.devices, chip_smoke.MESH_TREES, chip_smoke.MESH_TREE_DEPTH,
        validators=1 << 14, step_depth=12,
    )
    return {p.name: p for p in progs}


def test_sharded_tree_flush_compiles_for_four_described_chips(
    four_chip_programs, no_compile_cache
):
    """The served flush: the tree axis of a merkle_many dispatch split over
    the mesh, no collectives — a quarter of the trees a device."""
    row = chip_programs.compile_for(None, four_chip_programs["mesh:merkle_many"])
    # argument bytes are per device: its quarter of the flush
    flush = chip_smoke.MESH_TREES * (1 << chip_smoke.MESH_TREE_DEPTH) * 32
    assert row["argument_bytes"] == flush // 4


def test_sharded_epoch_step_compiles_for_four_described_chips(
    four_chip_programs, no_compile_cache
):
    """The sharded altair epoch + sharded tree step (``__graft_entry__``).
    Its reductions sum u64 balances across the mesh, and XLA:TPU has no
    64-bit all-reduce: a plain ``lax.psum`` of u64 is refused here
    ("Supported lowering only of Sum all reduce") though it passes on any
    number of virtual CPU devices. parallel/epoch.py sends 16-bit limbs."""
    _check_row(chip_programs.compile_for(None, four_chip_programs["mesh:epoch+tree"]))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_state_root_at_2_20_compiles_with_the_unrolled_chain_body(one_chip, no_compile_cache):
    """The served state-root program at a 2^20 registry: its four list
    tails ride ONE scan of 21 steps (20 zero-hash folds and the length
    mix) over [4, 8] roots, and that scan's body is the UNROLLED
    compression behind its fusion barrier, no round scan inside it,
    though four messages are far under sha256.SMALL_BATCH."""
    prog = _program("state_root")
    fn, args = prog.build()
    with chip_programs.as_accelerator():
        jaxpr = fn.trace(*args).jaxpr
    chains = [
        e for e in _eqns(jaxpr.jaxpr)
        if e.primitive.name == "scan" and e.outvars[0].aval.shape == (4, 8)
    ]
    assert [e.params["length"] for e in chains] == [21]
    body = {e.primitive.name for e in _eqns(chains[0].params["jaxpr"].jaxpr)}
    assert "optimization_barrier" in body
    assert not body & {"scan", "while"}
    _check_row(chip_programs.compile_for(one_chip, prog))


def _column_block_program(name: str) -> chip_programs.Program:
    """The two programs of a Fulu block's 128 data column sidecars at 21
    blobs (ops/das_batch), at the buckets the LIVE key functions give."""
    from eth_consensus_specs_tpu.analysis import kernels
    from eth_consensus_specs_tpu.ops import fr_fft, g1_msm
    from eth_consensus_specs_tpu.serve import buckets

    sidecars, blobs, points = 128, 21, 64
    if name == "das_fft":
        _, rows, segments = buckets.das_fold_key(sidecars * blobs, sidecars)
        stages = points.bit_length() - 1
        assert (rows, segments) == (4096, 128)
        return chip_programs.Program(
            name, lambda: (fr_fft._compiled_fold(points, stages),
                           kernels._das_fold_args(rows, segments, points, stages)))
    _, items, lanes = buckets.das_msm_key(2 * sidecars, blobs)
    assert (items, lanes) == (256, 32)
    return chip_programs.Program(
        name, lambda: (g1_msm.msm_many_kernel, kernels._kzg_msm_args(items, lanes)), limb=True)


@pytest.mark.parametrize(
    "name",
    # the limb kernel takes ~80 s to lower and compile here (~170 s before G1
    # ran on lazy limbs; the inverse FFT ~20 s): it is the one marked slow
    ["das_fft", pytest.param("das_msm", marks=pytest.mark.slow)],
)
def test_the_data_column_programs_compile_at_a_blocks_buckets(one_chip, no_compile_cache, name):
    """`peerdas_block_21.verify`'s two programs: the folding interpolation
    (4,096 rows of 64 points cut from 32-bit words, weighted and added into
    128 sidecars, a transform a sidecar) where the blob cell has 8 rows of
    4,096 points, 256 items x 32 lanes where it has 2 x 32."""
    _check_row(chip_programs.compile_for(one_chip, _column_block_program(name)))


def test_the_shuffle_program_compiles_at_the_2_20_lane_bucket(one_chip, no_compile_cache):
    """`committees_2p20.shuffle`'s one program at the bucket the LIVE key
    function gives a mainnet active set: the count a traced scalar, the
    368,640 decision blocks made inside it and hashed by the unrolled
    body, and 90 rounds that rotate the list and gather nothing."""
    from eth_consensus_specs_tpu.analysis import kernels
    from eth_consensus_specs_tpu.ops import shuffle
    from eth_consensus_specs_tpu.serve import buckets

    _, lanes = buckets.shuffle_key((1 << 20) - 4096 - 77)
    assert lanes == 1 << 20
    args = (kernels._sds((8,), "uint32"), kernels._sds((90,), "int32"),
            kernels._sds((), "int32"), kernels._sds((lanes,), "int32"))
    with chip_programs.as_accelerator():
        jaxpr = shuffle.shuffle_rounds_kernel.trace(*args).jaxpr
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert names.count("while") == 1 and "optimization_barrier" in names
    assert "gather" not in names and "scan" not in names
    row = chip_programs.compile_for(
        one_chip, chip_programs.Program("shuffle", lambda: (shuffle.shuffle_rounds_kernel, args)))
    _check_row(row)
    assert row["argument_bytes"] < 4 * lanes + 4096  # the list, the seed, the pivots, the count
