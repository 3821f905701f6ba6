"""The served slot path's device programs at the widths a slot dispatches
them — one list, compiled three ways.

``chip_smoke.py`` drives these programs on the chip; before it may, each
is compiled in the sandbox for a DESCRIBED TPU (``jax.experimental.
topologies``, no device attached) so that what the chip's compiler
refuses, or takes minutes over, is known at no chip time
(``scripts/tpu_compile_inventory.py`` runs them all and prints the
table; ``tests/test_tpu_compile.py`` keeps the ones that take seconds).

Shapes are never written here: every program takes its arguments from
the registry's own builders (``analysis/kernels.py`` ``_*_args``) and its
bucket from the LIVE serve key functions (``serve/buckets.py``), so the
inventory, the jaxlint registry and the dispatch agree by construction.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from functools import lru_cache
from typing import Callable, NamedTuple


class Program(NamedTuple):
    name: str  # the serve compile-key family it runs under
    build: Callable  # () -> (jitted fn, args); args are ShapeDtypeStruct pytrees
    limb: bool = False  # a curve-arithmetic graph: minutes to compile, not seconds


@lru_cache(maxsize=None)
def slot_world_shapes(n_validators: int):
    """(spec, static-array shapes, meta, forest plan, column shapes,
    justification shapes) of the slot world serve/slot.py boots at this
    registry size — abstract shapes only, no registry is materialised."""
    import jax

    from eth_consensus_specs_tpu.analysis import kernels
    from eth_consensus_specs_tpu.ops.altair_epoch import example_altair_inputs
    from eth_consensus_specs_tpu.ops.slot_pipeline import slot_spec
    from eth_consensus_specs_tpu.ops.state_root import forest_plan, synthetic_meta

    spec = slot_spec()
    meta = synthetic_meta(spec, n_validators)
    arrays, _, just = kernels._state_root_args(meta)
    cols = jax.eval_shape(lambda: example_altair_inputs(n_validators)[0])
    return spec, arrays, meta, forest_plan(meta), cols, just


def slot_programs(
    n_validators: int,
    committees: int,
    committee_size: int,
    sync_size: int,
    blobs: int,
    htr_trees: int,
    htr_depth: int,
) -> list[Program]:
    """Every program one mainnet-shaped slot, the boot before it and the
    two stateless verbs dispatch, in the order the cheap ones come first."""
    import jax

    from eth_consensus_specs_tpu.analysis import kernels
    from eth_consensus_specs_tpu.serve import buckets
    from eth_consensus_specs_tpu.serve.config import ServeConfig

    def w():
        return slot_world_shapes(n_validators)

    def sha_tile(tile):
        def build():
            from eth_consensus_specs_tpu.ops import sha256

            return sha256._kernel, (kernels._sds((tile, 16), "uint32"),)

        return build

    def merkle_many():
        from eth_consensus_specs_tpu.ops import merkle

        key = buckets.merkle_many_key(htr_trees, htr_depth, ServeConfig().buckets)
        fn = jax.jit(lambda words: merkle._many_tree_root_fused(words, htr_depth))
        return fn, kernels._merkle_many_args(key[1], htr_depth)

    def forest_build():
        from eth_consensus_specs_tpu.parallel import resident

        _, arrays, meta, plan, cols, _ = w()
        return resident._compiled_forest_builder(plan, meta), (
            arrays, cols.balance, cols.effective_balance, cols.inactivity_scores,
        )

    def resident_root():
        from eth_consensus_specs_tpu.ops import snapshot

        _, arrays, meta, plan, _, just = w()
        return snapshot._root_kernel(plan, meta), (
            arrays, kernels._forest_args(plan), just,
        )

    def resident_epoch():
        from eth_consensus_specs_tpu.ops.altair_epoch import AltairEpochParams
        from eth_consensus_specs_tpu.parallel import resident

        spec, arrays, meta, plan, cols, just = w()
        run = resident._compiled_runner(
            AltairEpochParams.from_spec(spec), 1, "state_inc", n_validators, 0,
            meta, plan, None,
        )
        return run, (
            cols, just, kernels._sds((8,), "uint32"), arrays,
            kernels._forest_args(plan),
        )

    def slot_apply():
        from eth_consensus_specs_tpu.ops import slot_pipeline

        _, _, meta, plan, _, _ = w()
        key = buckets.slot_key(
            n_validators, committees * committee_size, sync_size, plan
        )
        return (
            slot_pipeline._compiled_slot_apply(meta, plan, None, key[2], key[3]),
            kernels._slot_apply_args(meta, plan, key[2], key[3]),
        )

    def state_root():
        from eth_consensus_specs_tpu.ops import state_root as sr

        _, arrays, meta, _, cols, just = w()
        return sr._compiled_state_root(meta), (
            arrays, cols.balance, cols.effective_balance, cols.inactivity_scores, just,
        )

    def fr_fft():
        from eth_consensus_specs_tpu.ops import fr_fft as ff
        from eth_consensus_specs_tpu.ops.kzg_batch import N_BLOB

        key = buckets.fr_fft_key(blobs, N_BLOB)
        stages = N_BLOB.bit_length() - 1
        return ff._compiled_fft(N_BLOB, stages), kernels._fr_fft_args(
            key[1], N_BLOB, stages
        )

    def kzg_msm():
        from eth_consensus_specs_tpu.ops import g1_msm

        return g1_msm.msm_many_kernel, kernels._kzg_msm_args(
            2, buckets.kzg_msm_key(blobs)[1]
        )

    def g2_agg():
        from eth_consensus_specs_tpu.ops import g2_aggregate

        key = buckets.g2_agg_key(1, committees)
        return g2_aggregate.g2_sum_many_kernel, kernels._g2_agg_args(key[1], key[2])

    def bls_keysum():
        # the served committee sums (ops/bls_batch._served_pubkey_terms):
        # registry indices gathered from the resident key table and summed,
        # at the bucket a full block's 2 x committees aggregates land in
        from eth_consensus_specs_tpu.ops import g1_msm

        items, lanes = g1_msm.many_sum_shape(2 * committees, committee_size)
        table = kernels._sds((n_validators, g1_msm.N_LIMBS), "uint64")
        fn = jax.jit(
            lambda tx, ty, index: g1_msm.sum_indexed_kernel(
                tx, ty, index, strip=g1_msm.KEY_SUM_STRIP
            )
        )
        return fn, (table, table, kernels._sds((items, lanes), "int32"))

    from eth_consensus_specs_tpu.ops import sha256

    return [
        *(Program(f"sha256:tile{t}", sha_tile(t)) for t in sha256.TILES),
        Program("merkle_many", merkle_many),
        Program("forest_build", forest_build),
        Program("resident_root", resident_root),
        Program("slot_apply", slot_apply),
        Program("resident", resident_epoch),
        Program("state_root", state_root),
        Program("fr_fft", fr_fft),
        Program("kzg", kzg_msm, limb=True),
        Program("g2_agg", g2_agg, limb=True),
        Program("bls_keysum", bls_keysum, limb=True),
    ]


def mesh_programs(
    devices, trees: int, tree_depth: int, validators: int, step_depth: int
) -> list[Program]:
    """The two programs of ``chip_smoke.py --chips 4`` over a (dp, sp)
    mesh of ``devices`` (four described chips in the sandbox): the served
    merkle_many flush with its tree axis sharded, and the sharded altair
    epoch + sharded tree step (``parallel/epoch.sharded_step``). Each argument
    carries the NamedSharding its program gives it (compile with
    ``compile_for(None, ...)``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from eth_consensus_specs_tpu.ops import merkle
    from eth_consensus_specs_tpu.ops.altair_epoch import example_altair_inputs
    from eth_consensus_specs_tpu.parallel import epoch, make_mesh
    from eth_consensus_specs_tpu.parallel.mesh_ops import BATCH_AXES

    mesh = make_mesh(devices=list(devices))

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), tree, shardings
        )

    def served_flush():
        sds = jax.ShapeDtypeStruct(
            (trees, 1 << tree_depth, 8), jnp.uint32,
            sharding=NamedSharding(mesh, P(BATCH_AXES)),
        )
        return merkle._many_tree_root_sharded(mesh, tree_depth), (sds,)

    def sharded_step():
        _, stepped, (cols_sh, just_sh, leaves_sh) = epoch.sharded_step(mesh, step_depth)
        cols, just = jax.eval_shape(lambda: example_altair_inputs(validators, electra=True))
        leaves = jax.ShapeDtypeStruct((1 << step_depth, 8), jnp.uint32, sharding=leaves_sh)
        return stepped, (placed(cols, cols_sh), placed(just, just_sh), leaves)

    return [Program("mesh:merkle_many", served_flush), Program("mesh:epoch+tree", sharded_step)]


@contextmanager
def as_accelerator():
    """The kernels pick their graph per backend (``ops/sha256.py``:
    unrolled rounds on an accelerator, a round scan on XLA:CPU) by asking
    ``jax.default_backend()``, which in a sandbox answers ``cpu`` whatever
    the compile targets. Inside this block the question answers ``tpu``,
    so the graph that is lowered is the one the chip runs. Steering for
    the compile rehearsal only — JAX itself never calls the public
    attribute, and nothing executes in here."""
    import jax

    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real


def compile_for(device_sharding, program: Program) -> dict:
    """Lower and compile one program for the described device (or, with
    ``device_sharding=None``, for whatever shardings its arguments carry:
    memory_analysis then counts bytes PER device). Returns the inventory
    row: seconds to lower and to compile, generated-code
    bytes and the compiler's own memory analysis (bytes on the device)."""
    import jax

    fn, args = program.build()
    if device_sharding is not None:  # None: the arguments carry their own
        args = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=device_sharding),
            args,
        )
    with as_accelerator():
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    mem = compiled.memory_analysis()
    return {
        "program": program.name,
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "code_bytes": int(mem.generated_code_size_in_bytes),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
    }
