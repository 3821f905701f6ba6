"""Shape buckets, the shared device/host cost model, and compile accounting.

Every flush the service dispatches is padded into a SMALL set of
power-of-two shapes so the jitted kernels compile once per bucket
instead of once per observed batch size (XLA compiles per static shape;
an unbucketed service would recompile on every distinct (batch, depth)
it ever sees and spend its latency budget in the compiler). Two axes:

  * **tree depth** is intrinsic — padding a subtree to a deeper depth
    changes its root (the zero-hash fold differs), so depth is never
    padded; distinct depths are distinct buckets by construction;
  * **batch count** (trees per dispatch, requests per flush) IS padded:
    extra all-zero trees ride along and their roots are discarded.

This module is also the single home of the device/host *crossover cost
model*: ``DEVICE_SUBTREE_THRESHOLD`` (the leaf count above which the
device tree kernel beats per-level hashlib) lives here and is
re-exported by ``ops/merkle.py``, so the serving planner and the ops
entry point can never disagree about when the device is worth a
dispatch (tests/test_serve.py pins the crossover).

Compile accounting: every first dispatch of a new (op, *dims) shape key
is counted as ``serve.compiles`` (the jit cache makes later dispatches
free), its wall time recorded into the ``serve.compile_ms`` histogram
(via :class:`first_dispatch` — histogram count stays in lockstep with
the counter), appended to a persistent warmup list when
``ETH_SPECS_SERVE_WARMUP`` names a file, and ``precompile()`` replays
that list at startup so a restarted service pays zero compiles on its
steady-state buckets.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.analysis import lockwatch
from eth_consensus_specs_tpu.obs import waterfall

# Above this many leaf chunks PER DISPATCH the device tree kernel beats
# per-level hashlib (measured crossover, see ops/merkle.py's module doc
# for the dispatch-latency numbers that set it). A batched dispatch
# amortizes its fixed cost over every tree in the batch, so the model is
# expressed in TOTAL chunks: trees * chunks_per_tree.
DEVICE_SUBTREE_THRESHOLD = 4096


def device_subtree_worthwhile(n_chunks: int, trees: int = 1) -> bool:
    """One cost model for both the ops entry point (trees=1) and the
    service's bucket planner (trees=batch): device wins once the
    dispatch's total leaf chunks cross the threshold."""
    return trees * n_chunks >= DEVICE_SUBTREE_THRESHOLD


# Above this many TOTAL leaf chunks per dispatch the mesh-sharded path
# beats the single-device one (measured on the 8-virtual-device CPU
# mesh: 512 chunks = 0.4x — pure shard_map/collective overhead — while
# 2048 chunks already wins 7x; real accelerator meshes only move the
# crossover DOWN). Below it the service keeps the single-device bucket
# path; correctness is identical either way.
MESH_SUBTREE_THRESHOLD = 2048


def mesh_dispatch_worthwhile(n_chunks: int, trees: int = 1) -> bool:
    """Is a flush of `trees` subtrees x `n_chunks` leaf chunks big
    enough that sharding its tree axis over the mesh pays for the
    collective machinery?"""
    return trees * n_chunks >= MESH_SUBTREE_THRESHOLD


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(n - 1, 0).bit_length()


def batch_bucket(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest configured bucket that holds n items; the largest bucket
    caps the batcher's flush size, so n always fits."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


def mesh_batch_bucket(n: int, shards: int, buckets: tuple[int, ...]) -> int:
    """Mesh-aware padding target: the PER-SHARD tree count is what gets
    bucketed (smallest configured bucket >= ceil(n / shards)), and the
    dispatch pads to shards x that. For pow2 shard counts this equals the
    global bucket — same total padding, now split evenly — and for
    non-pow2 meshes it pads strictly less than the global pow2 would
    (an N-chip mesh must not 2x the padding waste just to stay pow2
    globally). Compile keys built from this carry the mesh signature, so
    a warmup artifact can never replay another mesh's shapes."""
    if shards <= 1:
        return batch_bucket(n, buckets)
    per = -(-n // shards)
    return shards * batch_bucket(per, buckets)


def subtree_depth(n_chunks: int) -> int:
    """Depth of the pow2 subtree holding n_chunks leaf chunks — the same
    depth a direct ``merkleize_subtree_device`` caller would pass, so
    service and direct roots are bit-identical."""
    return max(n_chunks - 1, 0).bit_length()


# ------------------------------------------- incremental dirty buckets --
#
# The incremental forest (ops/merkle_inc.py) compiles one path-update
# executable per DIRTY CAPACITY — the serve-buckets idiom applied to the
# dirty-leaf axis: a small pow2 set of capacities ever compiles, the
# live dirty count rides the smallest bucket that holds it, and the
# crossover cost model below decides when a dispatch should abandon the
# sparse path for the dense rebuild.

_INC_DIRTY_BUCKETS = (8, 64, 256, 1024, 4096, 16384, 65536)

# Work-ratio knob for the sparse/dense crossover: the sparse path costs
# ~(depth + leaf_hashes) compressions per dirty leaf but through
# gather/scatter at width K, while the dense rebuild's ~2^(d+1)
# compressions run at full vector width. Measured on this machine
# (XLA:CPU, depth 12-16 forests): the path update holds its hash-count
# advantage to roughly a QUARTER of break-even before the narrow-width
# dispatches lose to one wide rebuild — hence 0.25, env-overridable.
INC_CROSSOVER = 0.25


def inc_dirty_buckets() -> tuple[int, ...]:
    """The configured pow2 dirty-capacity buckets (env-snapshotted per
    call, never inside a trace — jit-purity)."""
    raw = os.environ.get("ETH_SPECS_INC_DIRTY_BUCKETS", "")
    if not raw:
        return _INC_DIRTY_BUCKETS
    try:
        vals = sorted({pow2_bucket(int(x)) for x in raw.split(",") if x.strip()})
    except ValueError:
        return _INC_DIRTY_BUCKETS
    return tuple(v for v in vals if v > 0) or _INC_DIRTY_BUCKETS


def inc_dirty_bucket(n_dirty: int) -> int:
    """Smallest configured dirty-capacity bucket holding `n_dirty`
    (the largest bucket caps it — past that the dense fallback is the
    plan, not a bigger compile)."""
    return batch_bucket(max(int(n_dirty), 1), inc_dirty_buckets())


def inc_crossover() -> float:
    """Sparse-vs-dense work-ratio crossover factor (env-snapshotted)."""
    raw = os.environ.get("ETH_SPECS_INC_CROSSOVER", "")
    try:
        return float(raw) if raw else INC_CROSSOVER
    except ValueError:
        return INC_CROSSOVER


def inc_dense_count(depth: int, cap: int, leaf_hashes: int = 0) -> int:
    """Dirty count above which one dense rebuild beats the path update
    for a depth-`depth` tree: break-even is ~2^(d+1) dense compressions
    against (depth + leaf_hashes + 1) per dirty leaf, scaled by the
    measured :data:`INC_CROSSOVER` constant factor and capped at the
    compile capacity (the sparse kernel cannot address more). This is
    the static threshold the `lax.cond` inside the update kernel routes
    on — data decides per dispatch, the model decides per compile."""
    dense_hashes = 2 << depth
    per_dirty = depth + leaf_hashes + 1
    return min(int(cap), max(1, int(inc_crossover() * dense_hashes / per_dirty)))


def merkle_inc_key(cap: int, dense_count: int, depth: int, mesh=None) -> tuple:
    """The compile/bucket/warmup key of one incremental forest update
    executable: every static knob of the kernel — dirty capacity bucket,
    dense-fallback threshold, GLOBAL tree depth — plus the mesh
    signature when the leaf axis shards (capacity and threshold apply
    per shard there). Single-device keys carry no signature, matching
    every other unsigned key family."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    shards = mesh_ops.shard_count(mesh)
    if shards > 1:
        return (
            "merkle_inc", int(cap), int(dense_count), int(depth),
            mesh_ops.mesh_signature(mesh),
        )
    return ("merkle_inc", int(cap), int(dense_count), int(depth))


# ------------------------------------------- aggregation (G2) buckets --
#
# The aggregation op (submit_aggregate / ops/g2_aggregate) sums RAGGED
# committees: the lane axis is the intrinsic compile axis (committee
# size, padded with infinity lanes) and — unlike the bls_msm family —
# it is also the axis the mesh shards, so the lane bucket is the
# mesh-aware one and the item bucket is a plain pow2.


def agg_mesh_lanes() -> int:
    """Smallest ragged-committee lane count worth sharding the G2
    aggregation dispatch's lane axis over the mesh (below it the
    all-gather combine costs more than the lanes it saves;
    env-snapshotted per call, never inside a trace — jit-purity)."""
    raw = os.environ.get("ETH_SPECS_AGG_MESH_LANES", "")
    try:
        return max(int(raw), 1) if raw else 8
    except ValueError:
        return 8


def agg_lane_bucket(n: int, shards: int = 1) -> int:
    """Lane-padding target of the aggregation op's ragged committee
    axis — :func:`mesh_batch_bucket` applied to the pow2 ladder, so the
    PER-SHARD lane count is what gets bucketed (the per-shard butterfly
    fold needs pow2 lanes) and the dispatch pads to shards x that. For
    pow2 shard counts this equals the global pow2; for non-pow2 meshes
    it pads strictly less (tests/test_serve_agg.py pins that)."""
    n = max(int(n), 1)
    per = -(-n // shards) if shards > 1 else n
    ladder = tuple(1 << i for i in range(max(per - 1, 0).bit_length() + 1))
    return mesh_batch_bucket(n, shards, ladder)


# --------------------------------------------------- KZG / DAS buckets --
#
# The blob-verification op (submit_blob_verify / ops/kzg_batch) runs two
# device dispatches per RLC check: ONE batched inverse fr_fft (blob
# polynomial -> coefficients, batch axis = blobs per flush) and ONE
# 2-item multi-MSM (the proof lincomb and the commitment-minus-y +
# proof-z lincomb as lanes of a single kernel). The MSM's LANE axis is
# what the mesh shards — a flush of n blobs folds into 2n+1 lanes — so
# the lane bucket is the signed compile axis, like g2_agg's.


def kzg_mesh_lanes() -> int:
    """Smallest RLC lane count worth sharding the KZG multi-MSM's lane
    axis over the mesh (below it the all-gather combine costs more than
    the double-and-add lanes it saves; env-snapshotted per call, never
    inside a trace — jit-purity)."""
    raw = os.environ.get("ETH_SPECS_KZG_MESH_LANES", "")
    try:
        return max(int(raw), 1) if raw else 16
    except ValueError:
        return 16


def kzg_lane_bucket(n_items: int, shards: int = 1) -> int:
    """Lane-padding target of the KZG RLC fold: a flush of n blobs
    needs 2n+1 lanes (commitments + proofs + the one generator lane),
    item-bucketed pow2 first so flush sizes collapse into few compiles,
    then padded per shard (the per-shard tree reduce needs pow2)."""
    n = pow2_bucket(max(int(n_items), 1))
    from eth_consensus_specs_tpu.ops.g1_msm import mesh_lane_pad

    return mesh_lane_pad(2 * n + 1, shards)


def kzg_msm_key_from_profile(n_items: int, shards: int = 1, sig: str = "") -> tuple:
    """:func:`kzg_msm_key` computed from a replica profile (shards,
    signature) instead of a live Mesh — same contract as
    :func:`bls_msm_key_from_profile`."""
    if shards > 1 and sig:
        return ("kzg", kzg_lane_bucket(n_items, shards), sig)
    return ("kzg", kzg_lane_bucket(n_items, 1))


def kzg_msm_key(n_items: int, mesh=None) -> tuple:
    """The compile/bucket/warmup key of the batched KZG RLC fold: the
    lane bucket of a 2-item multi-MSM over 2n+1 lanes, mesh-signed when
    the LANE axis shards. Single-device keys carry NO signature, like
    every other unsigned key family."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    return kzg_msm_key_from_profile(
        n_items, mesh_ops.shard_count(mesh), mesh_ops.mesh_signature(mesh)
    )


def fr_fft_key_from_profile(
    batch: int, n: int, shards: int = 1, sig: str = ""
) -> tuple:
    """:func:`fr_fft_key` computed from a replica profile — the batch
    axis buckets pow2 per shard (rows split evenly, no collectives)."""
    from eth_consensus_specs_tpu.ops.g1_msm import mesh_lane_pad

    if shards > 1 and sig:
        return ("fr_fft", mesh_lane_pad(batch, shards), int(n), sig)
    return ("fr_fft", pow2_bucket(max(int(batch), 1)), int(n))


def fr_fft_key(batch: int, n: int, mesh=None) -> tuple:
    """The compile/bucket/warmup key of a batched Fr FFT dispatch:
    pow2-bucketed batch (rows per flush) + the intrinsic FFT size, plus
    the mesh signature when the batch axis shards. The FFT had no
    bucket/key discipline at all before the DAS workload landed — every
    distinct blob-flush size was a fresh compile."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    return fr_fft_key_from_profile(
        batch, n, mesh_ops.shard_count(mesh), mesh_ops.mesh_signature(mesh)
    )


# ------------------------------------------------- live compile-key fns --
#
# The serve/bucket compile keys are FUNCTIONS here, not inline tuple
# construction at the dispatch sites, for one reason: the jaxlint
# recompile-surface rule (analysis/jaxlint.py) checks these exact
# callables for injectivity over the bucket grid — two traced signatures
# sharing one key is how the PR 8 mesh-signature bug class ships. The
# dispatch sites (serve/service.py, ops/bls_batch.py) and the analyzer
# calling the SAME function is what makes the check honest: a key edit
# that under-discriminates fails jaxlint before it can poison a warmup
# artifact.


def merkle_many_key_from_profile(
    n_trees: int, depth: int, buckets_cfg: tuple[int, ...],
    shards: int = 1, sig: str = "",
) -> tuple:
    """:func:`merkle_many_key` computed from a replica PROFILE — the
    (shard-count, mesh-signature) pair a router knows about a remote
    replica — instead of a live Mesh object. The front door uses this to
    predict which compile key a sibling would pay for a flush, which is
    what makes the warm-cache map honest; the jaxlint recompile-surface
    grid runs BOTH forms over the same bucket range, so a divergence
    between them is an ``aliased`` finding, not a silent cold compile."""
    if shards > 1 and sig:
        pad = mesh_batch_bucket(n_trees, shards, buckets_cfg)
        return ("merkle_many", pad, depth, sig)
    return ("merkle_many", batch_bucket(n_trees, buckets_cfg), depth)


def merkle_many_key(n_trees: int, depth: int, buckets_cfg: tuple[int, ...],
                    mesh=None) -> tuple:
    """The compile/bucket/warmup key of a merkle_many flush: bucket-padded
    tree count + depth, plus the mesh signature when the tree axis shards
    (same padded batch compiles once PER MESH — the signature is what
    keeps an 8-chip warmup artifact out of a 1-chip boot)."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    return merkle_many_key_from_profile(
        n_trees, depth, buckets_cfg,
        mesh_ops.shard_count(mesh), mesh_ops.mesh_signature(mesh),
    )


def bls_msm_key_from_profile(
    n_items: int, max_lanes: int, shards: int = 1, sig: str = ""
) -> tuple:
    """:func:`bls_msm_key` computed from a replica profile (shards,
    signature) instead of a live Mesh — same contract as
    :func:`merkle_many_key_from_profile`."""
    from eth_consensus_specs_tpu.ops.g1_msm import many_sum_shape

    shape = many_sum_shape(n_items, max_lanes, shards)
    if shards > 1 and sig:
        return ("bls_msm", *shape, sig)
    return ("bls_msm", *shape)


def bls_msm_key(n_items: int, max_lanes: int, mesh=None) -> tuple:
    """The compile/bucket/warmup key of the batched per-item G1 many-sum
    dispatch: the shared many_sum_shape (items, lanes) bucket, mesh-signed
    when the item axis shards. Single-device keys carry NO signature —
    byte-compatible with every warmup artifact written before mesh
    dispatch existed."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    return bls_msm_key_from_profile(
        n_items, max_lanes, mesh_ops.shard_count(mesh), mesh_ops.mesh_signature(mesh)
    )


def bls_keysum_key(n_items: int, max_lanes: int, registry: int, mesh=None) -> tuple:
    """The compile/bucket/warmup key of the committee sums gathered from
    the registry's key table on the device (``g1_msm.sum_indexed_kernel``):
    the many_sum_shape (items, lanes) bucket and the table's length, which
    is a dimension of the program too; mesh-signed when the item axis
    shards."""
    from eth_consensus_specs_tpu.ops.g1_msm import many_sum_shape
    from eth_consensus_specs_tpu.parallel import mesh_ops

    shards = mesh_ops.shard_count(mesh)
    key = ("bls_keysum", *many_sum_shape(n_items, max_lanes, shards), int(registry))
    return (*key, mesh_ops.mesh_signature(mesh)) if shards > 1 else key


def das_msm_key(n_items: int, max_lanes: int) -> tuple:
    """The compile/bucket/warmup key of a flush of data column sidecars'
    multi-MSM (``g1_msm.msm_many_kernel`` through ``ops/das_batch``): two
    items a sidecar and a lane a proof, each pow2-bucketed. Another family
    than ``kzg``, whose program is the same kernel at two items: a block
    of 128 sidecars of 21 blobs is 256 x 32. One chip holds the block, so
    the key is never mesh-signed. The flush's interpolation runs under
    :func:`das_fold_key`."""
    return ("das_msm", pow2_bucket(max(int(n_items), 1)), pow2_bucket(max(int(max_lanes), 1)))


def das_fold_key(cells: int, sidecars: int) -> tuple:
    """The key of a data column flush's folding interpolation
    (``ops/fr_fft.fold_program`` through ``ops/das_batch``): the row
    bucket of its cells, :func:`fr_fft_key`'s at 64 points, and the
    sidecar bucket, half of :func:`das_msm_key`'s items. A block of 128
    sidecars of 21 blobs is 4,096 x 128."""
    return ("das_fold", pow2_bucket(max(int(cells), 1)), pow2_bucket(max(int(sidecars), 1)))


def shuffle_key(n: int) -> tuple:
    """The compile/bucket/warmup key of an epoch's shuffle
    (``ops/shuffle.shuffle_rounds_kernel``): the lane bucket, the power of
    two at or above the active count, which the program takes as a traced
    number. One chip holds the list, so the key is never mesh-signed."""
    return ("shuffle", pow2_bucket(max(int(n), 1)))


def g2_agg_key_from_profile(
    n_items: int, max_lanes: int, shards: int = 1, sig: str = ""
) -> tuple:
    """:func:`g2_agg_key` computed from a replica profile (shards,
    signature) instead of a live Mesh — same contract as
    :func:`bls_msm_key_from_profile`. Items bucket pow2 (the item axis
    replicates across shards), lanes through the mesh-aware
    :func:`agg_lane_bucket`."""
    if shards > 1 and sig:
        return (
            "g2_agg",
            pow2_bucket(max(n_items, 1)),
            agg_lane_bucket(max_lanes, shards),
            sig,
        )
    return ("g2_agg", pow2_bucket(max(n_items, 1)), agg_lane_bucket(max_lanes, 1))


def g2_agg_key(n_items: int, max_lanes: int, mesh=None) -> tuple:
    """The compile/bucket/warmup key of the batched G2 committee-sum
    dispatch: the shared g2_many_sum_shape (items, lanes) bucket,
    mesh-signed when the LANE axis shards. Single-device keys carry NO
    signature, like every other unsigned key family."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    return g2_agg_key_from_profile(
        n_items, max_lanes, mesh_ops.shard_count(mesh), mesh_ops.mesh_signature(mesh)
    )


def slot_key_from_profile(
    n_validators: int,
    cap_flags: int,
    cap_rewards: int,
    cap_val: int,
    cap_bal: int,
    shards: int = 1,
    sig: str = "",
) -> tuple:
    """:func:`slot_key` computed from a replica profile — same contract
    as :func:`bls_msm_key_from_profile`. The capacities are the
    REQUEST-derived update counts (every set committee bit / sync
    index, pre-verdict: ``ops.slot_pipeline.request_capacity``), pow2
    bucketed; the forest-plan dirty capacities ride the key because the
    fused re-root compiles per plan exactly like the resident runner."""
    key = (
        "slot_apply",
        int(n_validators),
        pow2_bucket(max(int(cap_flags), 1)),
        pow2_bucket(max(int(cap_rewards), 1)),
        int(cap_val),
        int(cap_bal),
    )
    if shards > 1 and sig:
        return (*key, sig)
    return key


def slot_key(n_validators: int, n_flags: int, n_rewards: int, plan, mesh=None) -> tuple:
    """The compile/bucket/warmup key of the fused slot-apply dispatch
    (participation/balance scatter + incremental re-root against the
    resident forest — the whole-slot pipeline's one stateful kernel):
    registry size + pow2-bucketed update capacities + the forest plan's
    dirty-capacity buckets, mesh-signed only when the forest itself
    shards (plan.shards > 1 — the slot world's forest is single-device
    today, so live keys are unsigned like every other unsigned family)."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    return slot_key_from_profile(
        n_validators,
        n_flags,
        n_rewards,
        int(plan.cap_val),
        int(plan.cap_bal),
        int(plan.shards),
        mesh_ops.mesh_signature(mesh) if int(plan.shards) > 1 else "",
    )


# ------------------------------------------------- fleet routing model --
#
# The two-tier fleet (serve/frontdoor.py) routes by (compile-shape,
# mesh-signature): a request's intrinsic shape decides WHICH replica
# tier should serve it, and a replica's replayed warmup keys decide
# whether it can serve the shape without a cold compile. Both policies
# are LIVE functions here so the router, the bench, and the analysis
# key grids can never disagree about them.


def route_wide(kind: str, dim: int, max_batch: int) -> bool:
    """Does a request of this kind / intrinsic dim belong on a WIDE
    (mesh-sliced) replica? htr: the steady-state flush — ``max_batch``
    trees of ``2^dim`` chunks — must clear the measured mesh crossover
    (:func:`mesh_dispatch_worthwhile`); below it the sharded path LOSES
    to collective overhead and the request belongs on a narrow replica.
    bls: the mesh shards the flush's ITEM axis, so any full flush past
    the min-items floor is wide-worthy regardless of committee size."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    if kind in ("htr", "merkle_many"):
        return mesh_dispatch_worthwhile(1 << dim, max(int(max_batch), 1))
    if kind in ("agg", "g2_agg"):
        # the G2 aggregation shards its LANE axis: the request's
        # intrinsic dim is its pow2 committee-lane bucket, wide once it
        # clears the lane crossover regardless of flush size
        return int(dim) >= agg_mesh_lanes()
    if kind == "kzg":
        # the KZG RLC fold shards its LANE axis too: `dim` is the lane
        # bucket the flush folds into (2n+1 lanes, pow2-bucketed)
        return int(dim) >= kzg_mesh_lanes()
    if kind == "slot":
        # the slot pipeline's stateful leg (the resident forest) is
        # single-device; its verify/aggregate legs shard internally.
        # Routing is OWNERSHIP, not width — never mesh-routed here.
        return False
    return int(max_batch) >= mesh_ops.min_items()


def route_shape_of_key(key: tuple) -> tuple | None:
    """The router-visible (op, intrinsic-dim) a compiled shape key warms:
    merkle_many keys warm their DEPTH (batch padding is bucket policy,
    not identity), bls_msm keys warm their lane bucket (the pow2
    committee the client hashes by). Unknown ops warm nothing."""
    op = key[0]
    dims = [d for d in key[1:] if not isinstance(d, str)]
    if op == "merkle_many" and len(dims) == 2:
        return (op, int(dims[1]))
    if op in ("bls_msm", "g2_agg", "kzg") and dims:
        return (op, int(dims[-1]))
    if op == "fr_fft" and len(dims) == 2:
        return (op, int(dims[1]))  # the intrinsic FFT size
    if op == "slot_apply" and len(dims) >= 4:
        return ("slot", int(dims[1]))  # the flag-capacity bucket
    return None


def widen_warm_keys(
    keys: list[tuple] | None, cfg, shards: int, sig: str
) -> list[tuple]:
    """The per-replica warm-key list for one mesh profile: the caller's
    unsigned workload keys plus, for a wide profile, the mesh-signed
    variants that replica's dispatches will actually compile — signed
    merkle pads for every flush size past the crossover, signed bls_msm
    shapes for every item bucket. A narrow profile gets the unsigned
    list verbatim; an alien-signed key never appears (precompile would
    skip it anyway, but the point of per-profile lists is that the
    respawned replacement replays ONLY its own mesh's keys)."""
    from eth_consensus_specs_tpu.parallel import mesh_ops

    out = [tuple(k) for k in keys or []]
    if shards <= 1 or not sig:
        return out
    floor = mesh_ops.min_items()
    depths = sorted({k[2] for k in out if k[0] == "merkle_many" and len(k) == 3})
    for depth in depths:
        pads = sorted(
            {
                mesh_batch_bucket(n, shards, cfg.buckets)
                for n in range(1, cfg.max_batch + 1)
                if n >= floor and mesh_dispatch_worthwhile(1 << depth, n)
            }
        )
        out += [("merkle_many", pad, int(depth), sig) for pad in pads]
    lanes = sorted({k[2] for k in out if k[0] == "bls_msm" and len(k) == 3})
    for lane in lanes:
        # signed pads are generated from LIVE flush counts (like the
        # merkle branch above), not from the unsigned keys' already-
        # padded item counts: mesh_lane_pad is only idempotent under
        # that round-trip for pow2 shard counts, and a 6-shard replica
        # fed pad-of-pad keys would cold-compile its real flush shapes
        out += [
            bls_msm_key_from_profile(n, lane, shards, sig)
            for n in range(1, cfg.max_batch + 1)
            if n >= floor
        ]
    agg_lanes = sorted({k[2] for k in out if k[0] == "g2_agg" and len(k) == 3})
    for lane in agg_lanes:
        if lane < agg_mesh_lanes():
            continue  # lanes below the crossover never shard: no signed shape
        # signed lane pads from the RAW lane counts that bucket to this
        # pow2: the service pads from the live flush's raw max, and
        # agg_lane_bucket is only pad-of-pad idempotent for pow2 shard
        # counts — the same lesson as the bls branch above, applied to
        # the lane axis because that is what this family shards
        pads = sorted(
            {agg_lane_bucket(x, shards) for x in range(lane // 2 + 1, lane + 1)}
        )
        items = sorted({pow2_bucket(n) for n in range(1, cfg.max_batch + 1)})
        out += [("g2_agg", it, pad, sig) for it in items for pad in pads]
    if any(k[0] == "kzg" and len(k) == 2 for k in out):
        # signed RLC-fold lanes from the LIVE flush counts whose lane
        # bucket clears the kzg crossover — the same lesson as the bls
        # branch (pad-of-pad is only idempotent for pow2 shard counts)
        out += [
            kzg_msm_key_from_profile(n, shards, sig)
            for n in range(1, cfg.max_batch + 1)
            if kzg_lane_bucket(n, 1) >= kzg_mesh_lanes()
        ]
    fft_sizes = sorted({k[2] for k in out if k[0] == "fr_fft" and len(k) == 3})
    for nfft in fft_sizes:
        out += [
            fr_fft_key_from_profile(b, nfft, shards, sig)
            for b in range(1, cfg.max_batch + 1)
            if b >= floor
        ]
    # distinct flush sizes can pad to one compile shape: dedupe, keep order
    return list(dict.fromkeys(out))


# ------------------------------------------------- compile accounting --

_SEEN_LOCK = lockwatch.wrap(threading.Lock(), "serve.buckets._SEEN_LOCK")
_SEEN_SHAPES: set[tuple] = set()


def _reinit_lock_after_fork_in_child() -> None:
    # fork-safety: replica boots and gen-pool forks happen while serving
    # threads may be inside note_dispatch; the child re-creates the lock
    global _SEEN_LOCK
    _SEEN_LOCK = lockwatch.wrap(threading.Lock(), "serve.buckets._SEEN_LOCK")


os.register_at_fork(after_in_child=_reinit_lock_after_fork_in_child)


def note_dispatch(op: str, *dims) -> bool:
    """Record a dispatch of shape key (op, *dims). Returns True (and
    bumps ``serve.compiles``) on the FIRST sighting — the dispatch that
    pays the jit compile — False for every shape the process has already
    compiled. Dims are ints plus, for mesh-sharded shapes, the mesh
    signature string (parallel/mesh_ops.mesh_signature) — the same
    padded batch compiles per mesh, and the warmup artifact must say
    which. The counter is what the bench asserts 'at most len(buckets)
    compiles after warmup' against."""
    key = (op, *(d if isinstance(d, str) else int(d) for d in dims))
    with _SEEN_LOCK:
        if key in _SEEN_SHAPES:
            return False
        _SEEN_SHAPES.add(key)
    obs.count("serve.compiles", 1)
    obs.event("serve.compile", op=op, dims=",".join(map(str, dims)))
    _warmup_append(key)
    return True


def is_compiled(op: str, *dims) -> bool:
    """Whether this process has dispatched (so compiled, or loaded from
    the persistent cache) the shape key already. A dispatch site whose
    program takes minutes to compile asks before it takes the device, and
    goes to the host otherwise: ``precompile`` is what warms such a key."""
    key = (op, *(d if isinstance(d, str) else int(d) for d in dims))
    with _SEEN_LOCK:
        return key in _SEEN_SHAPES


def observe_compile_ms(op: str, ms: float, n: int = 1) -> None:
    """Record a first-dispatch compile wall time into the
    ``serve.compile_ms`` (+ per-op) histograms. ``n > 1`` records the
    same wall once per first-sighted shape that paid inside it (the BLS
    MSM case: several pow2 committee sizes can first-compile inside one
    ``verify_many`` call) — the invariant ``serve.compile_ms.count ==
    serve.compiles`` is what serve_bench and the CI obs-report job
    assert."""
    for _ in range(max(n, 0)):
        obs.observe("serve.compile_ms", ms)
        obs.observe(f"serve.compile_ms.{op}", ms)


def _live_array_bytes() -> int:
    """Total nbytes across the process's live device arrays; 0 when jax
    (or the live_arrays probe) is unavailable. Only the first-dispatch
    path pays this walk — once per compile, never per dispatch."""
    try:
        import jax

        return sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())
    except Exception:
        return 0


class first_dispatch:
    """``with first_dispatch(op, *dims):`` around the dispatch call —
    notes the shape key (``serve.compiles`` on first sighting) and, when
    this dispatch is the one paying the jit compile, records its wall
    time into ``serve.compile_ms``. The wall is recorded even when the
    block raises: the compile attempt happened and the histogram must
    stay in lockstep with the ``serve.compiles`` counter.

    A first dispatch also posts the HBM ledger's ``jit_cache`` entry
    (obs/ledger.py): the growth in live device-array bytes across the
    compile — captured constants, donated staging buffers, and the
    result the warm cache will keep reusing. An approximation (XLA's
    executable itself is not a jax array), but it is the bytes a warm
    cache pins that the resident-state/forest owners don't account."""

    __slots__ = ("op", "dims", "first", "_t0", "_live0")

    def __init__(self, op: str, *dims):
        self.op = op
        self.dims = dims

    def __enter__(self) -> "first_dispatch":
        self.first = note_dispatch(self.op, *self.dims)
        if self.first:
            self._live0 = _live_array_bytes()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.first:
            observe_compile_ms(self.op, (time.perf_counter() - self._t0) * 1e3)
            if exc_type is None:
                grown = _live_array_bytes() - self._live0
                if grown > 0:
                    from eth_consensus_specs_tpu.obs import ledger

                    ledger.register(
                        "jit_cache",
                        "-".join((self.op, *map(str, self.dims))),
                        grown,
                    )
        return False


def seen_shapes() -> list[tuple]:
    with _SEEN_LOCK:
        return sorted(_SEEN_SHAPES)


def reset_for_tests() -> None:
    with _SEEN_LOCK:
        _SEEN_SHAPES.clear()


# ------------------------------------------------- persistent warmup --


def warmup_path() -> str | None:
    return os.environ.get("ETH_SPECS_SERVE_WARMUP") or None


def _warmup_append(key: tuple) -> None:
    path = warmup_path()
    if path is None:
        return
    try:
        existing = set(map(tuple, load_warmup(path)))
        if key in existing:
            return
        with open(path, "a") as fh:
            fh.write(json.dumps(list(key)) + "\n")
    except OSError:
        pass  # warmup persistence is best-effort; serving never blocks on it


def write_warmup(path: str, keys: list[tuple] | None = None) -> int:
    """Write the warmup artifact in one shot (atomic replace): every
    shape key this process has compiled, or an explicit list. This is
    the shippable form — ``serve_bench.py --warmup-out`` emits it, CI
    uploads it, replica boots replay it via ``precompile(path=...)``."""
    keys = seen_shapes() if keys is None else [tuple(k) for k in keys]
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as fh:
        for key in keys:
            fh.write(json.dumps(list(key)) + "\n")
    os.replace(tmp, path)
    return len(keys)


def load_warmup(path: str | None = None) -> list[tuple]:
    """Shape keys recorded by previous runs (JSONL, one ``[op, *dims]``
    per line; torn/alien lines are skipped, not trusted)."""
    path = path or warmup_path()
    if path is None or not os.path.exists(path):
        return []
    out: list[tuple] = []
    try:
        with open(path) as fh:
            for line in fh:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if isinstance(row, list) and row and isinstance(row[0], str):
                    out.append(tuple(row))
    except OSError:
        return []
    return out


def _key_mesh(dims: tuple, chips: int | None = None):
    """Split (.., sig?) trailing mesh signature off a shape key and
    resolve it against the live serve mesh — `chips` overrides the env
    default so a caller dispatching on an explicit sub-mesh (bench
    --chips, ServeConfig.mesh_chips) warms ITS mesh's keys, not the
    whole host's: (int_dims, mesh, ok). A key from another mesh shape
    (or a mesh key replayed without a live mesh) is skipped, never
    compiled wrong — ok=False."""
    from eth_consensus_specs_tpu.parallel.mesh_ops import mesh_signature, serve_mesh

    if not (dims and isinstance(dims[-1], str)):
        return tuple(int(d) for d in dims), None, True
    sig = dims[-1]
    mesh = serve_mesh(chips)
    if mesh is None or mesh_signature(mesh) != sig:
        return tuple(int(d) for d in dims[:-1]), None, False
    return tuple(int(d) for d in dims[:-1]), mesh, True


@contextlib.contextmanager
def _warming(op: str, *dims):
    """One key of :func:`precompile`: its first dispatch, inside the leg
    ``precompile.<op>``. The leg names what XLA traced, lowered, read or
    compiled for the key (``xla.*_ms.precompile.<op>``) where the op's own
    code opens no leg further in (``bls_keysum``)."""
    with waterfall.leg(f"precompile.{op}"), first_dispatch(op, *dims):
        yield


def _warm_das_fold(rows: int, segments: int) -> None:
    from eth_consensus_specs_tpu.ops import das_batch

    with _warming("das_fold", rows, segments):
        das_batch.warm_fold(rows, segments)


def precompile(
    keys: list[tuple] | None = None, path: str | None = None, chips: int | None = None,
    key_table=None,
) -> int:
    """Compile every known bucket shape ahead of traffic. With no
    explicit `keys`, replays the persistent warmup list — from ``path``
    when given (the SHIPPABLE warmup artifact: one replica or a CI run
    writes it, every later boot consumes it), else from
    ``ETH_SPECS_SERVE_WARMUP``. Returns the number of shapes warmed.
    Unknown ops are skipped (a warmup file written by a newer version
    must not crash an older server), and mesh-signed keys are replayed
    ONLY when the live serve mesh matches the signature — an 8-chip
    artifact must not poison a single-chip boot with alien shapes
    (``serve.precompile_skipped`` event per skip). ``key_table`` is the
    service's registry of public keys (ops/key_table.py), which the
    ``bls_keysum`` programs gather from; their keys are skipped without
    it, or where it has another length than the key names. A
    ``das_msm`` key is what sends a flush of data column sidecars to the
    device: it warms the multi-MSM and, under its own ``das_fold`` key,
    the folding interpolation of the block that fills the bucket (every
    sidecar as wide as the lane bucket; a ``das_fold`` key warms that
    program at any other shape); a
    ``shuffle`` key does the same for committee requests of its lane
    bucket. A key is warmed inside the leg ``precompile.<op>``."""
    import numpy as np

    warmed = 0
    for key in keys if keys is not None else load_warmup(path):
        op, dims = key[0], key[1:]
        try:
            int_dims, mesh, ok = _key_mesh(tuple(dims), chips)
            if not ok:
                obs.event(
                    "serve.precompile_skipped",
                    op=op,
                    dims=",".join(map(str, dims)),
                    reason="mesh-signature mismatch",
                )
                continue
            if op == "merkle_many" and len(int_dims) == 2:
                from eth_consensus_specs_tpu.ops.merkle import merkleize_many_device

                batch, depth = int_dims
                zero = np.zeros((1, 8), np.uint32)
                # warmup compiles are first dispatches like any other:
                # their wall time lands in serve.compile_ms too
                with _warming(op, *dims):
                    merkleize_many_device([zero], depth, pad_batch=batch, mesh=mesh)
            elif op == "bls_msm" and len(int_dims) in (1, 2):
                from eth_consensus_specs_tpu.crypto.curve import g1_generator
                from eth_consensus_specs_tpu.ops.bls_batch import _use_device
                from eth_consensus_specs_tpu.ops.g1_msm import sum_g1_many_device

                if mesh is None and not _use_device():
                    # without a mesh only the switch-routed batch path
                    # dispatches this kernel: nothing to warm on the host backend
                    continue
                # legacy 1-dim keys are (lanes,); current keys are
                # (items, lanes[, sig]) — the many_sum_shape bucket. One
                # throwaway point at exactly the padded shape: the sum is
                # discarded, only the kernel compile matters
                items, lanes = (1, int_dims[0]) if len(int_dims) == 1 else int_dims
                with _warming(op, *dims):
                    sum_g1_many_device(
                        [[g1_generator()]], mesh=mesh, pad_shape=(items, lanes)
                    )
            elif op == "bls_keysum" and len(int_dims) == 3:
                from eth_consensus_specs_tpu.ops.g1_msm import sum_indexed_device

                items, lanes, registry = int_dims
                if key_table is None or len(key_table) != registry:
                    continue  # another registry's program
                with _warming(op, *dims):
                    sum_indexed_device(
                        key_table.device_limbs(mesh), [np.zeros(1, np.int32)],
                        (items, lanes), mesh=mesh,
                    )
            elif op == "kzg" and len(int_dims) == 1:
                from eth_consensus_specs_tpu.crypto.curve import g1_generator
                from eth_consensus_specs_tpu.ops.g1_msm import msm_g1_many_device

                # one throwaway lane per item at exactly the padded
                # lane shape: results discarded, only the 2-item
                # multi-MSM kernel compile matters
                lanes = int_dims[0]
                with _warming(op, *dims):
                    msm_g1_many_device(
                        [[g1_generator()]] * 2, [[1]] * 2,
                        mesh=mesh, pad_shape=(2, lanes),
                    )
            elif op == "das_msm" and len(int_dims) == 2 and mesh is None:
                from eth_consensus_specs_tpu.crypto.curve import g1_generator
                from eth_consensus_specs_tpu.ops.g1_msm import msm_g1_many_device

                # one throwaway lane at exactly the padded shape; only a
                # warmed bucket's flushes go to the device (ops/das_batch.py)
                with _warming(op, *dims):
                    msm_g1_many_device([[g1_generator()]], [[1]], pad_shape=int_dims)
                items, lanes = int_dims
                _warm_das_fold(*das_fold_key(items // 2 * lanes, items // 2)[1:])
            elif op == "das_fold" and len(int_dims) == 2 and mesh is None:
                _warm_das_fold(*int_dims)
            elif op == "shuffle" and len(int_dims) == 1 and mesh is None:
                from eth_consensus_specs_tpu.ops.shuffle import (
                    mainnet_rounds,
                    shuffled_indices_device,
                )

                # one live lane under the bucket: the count is a traced
                # number, so this is the program every count below it runs
                with _warming(op, *dims):
                    shuffled_indices_device(
                        np.zeros(1, np.int32), bytes(32), mainnet_rounds(),
                        lanes=int_dims[0],
                    )
            elif op == "fr_fft" and len(int_dims) == 2:
                from eth_consensus_specs_tpu.crypto.kzg import compute_roots_of_unity
                from eth_consensus_specs_tpu.ops.fr_fft import batch_fft_field

                # one zero row padded to the bucketed batch: the
                # inverse and forward tables share one executable
                # (twiddles are traced args), so either direction warms
                batch, nfft = int_dims
                with _warming(op, *dims):
                    batch_fft_field(
                        [[0] * nfft], compute_roots_of_unity(nfft),
                        inv=True, mesh=mesh, pad_batch=batch,
                    )
            elif op == "g2_agg" and len(int_dims) == 2:
                from eth_consensus_specs_tpu.crypto.curve import g2_generator
                from eth_consensus_specs_tpu.ops.g2_aggregate import sum_g2_many_device

                # throwaway committees at exactly the padded shape: the
                # sums are discarded, only the (items, lanes[, mesh])
                # kernel compile matters
                items, lanes = int_dims
                with _warming(op, *dims):
                    sum_g2_many_device(
                        [[g2_generator()] * lanes] * items,
                        mesh=mesh,
                        pad_shape=(items, lanes),
                    )
            elif op == "slot_apply" and len(int_dims) == 5:
                from eth_consensus_specs_tpu.serve import slot as serve_slot

                # AOT lower+compile of the fused slot-apply executable
                # (no live forest touched); skips — not fails — when the
                # key's forest-plan caps don't match this build
                with waterfall.leg("precompile.slot_apply"):
                    done = serve_slot.precompile_key((op, *int_dims), mesh=mesh)
                if not done:
                    continue
            else:
                continue
        except Exception:
            obs.event("serve.precompile_failed", op=op, dims=",".join(map(str, dims)))
            continue
        warmed += 1
    if warmed:
        obs.count("serve.precompiled", warmed)
    return warmed
