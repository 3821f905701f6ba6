"""Batched G2 many-sum — the aggregation pipeline's device kernel.

The serve layer has always *verified* pre-aggregated signatures; a
consensus node spends its slot budget *building* them from the
million-validator attestation fan-in. Signatures live in G2 (minimal-
pubkey-size BLS), so the hot operation is a ragged segment sum over
``g2_jacobian.g2_add`` lanes: every committee of a flush sums in ONE
dispatch, mirroring ``g1_msm.sum_many_kernel``'s one-dispatch-per-flush
discipline on the pubkey side.

Kernel shape: X/Y/Z are uint64[I, L, 2, 15] Montgomery lazy-limb arrays
(ops/lazy_limbs; infinity encoded as Z == 0, exactly the convention
``crypto/curve`` converts 1:1). Ragged committees pad their lane axis
with infinity lanes. The reduction is a LOG-DEPTH pairwise tree in
butterfly form, run as ONE ``lax.scan`` over the log2(L) levels: step s
adds every lane to its ``lane XOR 2^s`` partner, so after the scan lane
0 holds the committee sum and — crucially — the expensive complete-add
graph compiles ONCE per shape instead of once per tree level (measured
on XLA:CPU: ~45 s for the scan body vs ~50 s PER unrolled level). The
carry crosses the scan boundary canonical (limbs < 2^26, value < 2p),
the same bound discipline as ``g2_jacobian.g2_mul_z``.

Mesh variant: the LANE axis shards over the (dp, sp) mesh — each shard
folds its lane slice locally, then the per-shard Jacobian partials
all-gather and fold again on every device (the replicated-top combine
idiom of ``merkle_inc``/``msm_g1_device``). Jacobian addition is exact
group math and the final affine conversion is canonical, so any shard
count returns byte-identical points.

Conversion boundary: affine ``crypto/curve.Point`` <-> Montgomery limb
arrays on host; the final Jacobian->affine Fq2 inversion also stays
host-side (one inverse per committee, not worth a device Fermat chain
at flush sizes).
"""

from __future__ import annotations

import os

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto.curve import B2, Point, g2_infinity
from eth_consensus_specs_tpu.crypto.fields import Fq, Fq2
from eth_consensus_specs_tpu.ops import fq12_tower as tw
from eth_consensus_specs_tpu.ops import g2_jacobian as gj
from eth_consensus_specs_tpu.ops import lazy_limbs as lz
from eth_consensus_specs_tpu.ops.lazy_limbs import lf


def _butterfly_partners(lanes: int) -> np.ndarray:
    """Per-level partner indices of the log-depth pairwise reduction in
    butterfly form: ``partners[s, j] = j XOR 2^s``. int32 on purpose —
    a python-int iota would widen to i64 under the package-wide x64
    flag (jaxlint x64-drift)."""
    steps = max(lanes - 1, 0).bit_length()
    if steps == 0:
        return np.zeros((0, lanes), np.int32)
    idx = np.arange(lanes, dtype=np.int32)
    return np.stack([idx ^ (1 << s) for s in range(steps)]).astype(np.int32)


def _lane_fold(X, Y, Z, axis: int = 1):
    """Sum the ``axis`` lanes of Jacobian [.., L, .., 2, 15] coordinate
    arrays via the butterfly tree; returns the [.., 2, 15] sums (lane 0
    of the folded axis). Lane count must be a power of two; infinity
    lanes (Z == 0) are absorbed by the complete add."""
    if axis != 1:
        X, Y, Z = (jnp.moveaxis(a, axis, 1) for a in (X, Y, Z))
    lanes = X.shape[1]
    # XOR partners index past the edge on a non-pow2 width, and
    # jnp.take would CLIP them silently — wrong sums, not an error
    assert lanes & (lanes - 1) == 0, f"lane fold needs pow2 lanes, got {lanes}"
    partners = _butterfly_partners(lanes)
    if partners.shape[0] == 0:
        return X[:, 0], Y[:, 0], Z[:, 0]

    def step(carry, idx):
        cX, cY, cZ = carry
        p = gj.G2J(lf(cX), lf(cY), lf(cZ))
        q = gj.G2J(
            lf(jnp.take(cX, idx, axis=1)),
            lf(jnp.take(cY, idx, axis=1)),
            lf(jnp.take(cZ, idx, axis=1)),
        )
        s = gj.g2_add(p, q)
        # canonical across the scan boundary: the re-wrap on entry
        # (lf = limbs < 2^26, value < 2p) must tell the truth
        return (gj._canon(s.x).v, gj._canon(s.y).v, gj._canon(s.z).v), None

    (oX, oY, oZ), _ = lax.scan(step, (X, Y, Z), jnp.asarray(partners))
    return oX[:, 0], oY[:, 0], oZ[:, 0]


@jax.jit
def g2_sum_many_kernel(X, Y, Z):
    """Per-item G2 point sums over [I, L, 2, 15] lane arrays (L a power
    of two): the batched committee-aggregate kernel — one dispatch sums
    every committee of a flush instead of one dispatch per committee."""
    return _lane_fold(X, Y, Z)


# == mesh-sharded kernel ===================================================
#
# The LANE axis shards over the (dp, sp) mesh: each shard's committees
# are the same (the item axis replicates), its lane slice folds locally,
# and the per-shard [I, 2, 15] Jacobian partials all-gather + fold again
# on every device — the replicated-top combine of merkle_inc and
# msm_g1_device. Per-shard lane counts stay a power of two by the
# agg_lane_bucket padding model (serve/buckets.py).


def _cross_shard_fold(rX, rY, rZ, axes):
    """all_gather per-shard Jacobian partials ([I, 2, 15] each) and fold
    the shard axis; non-pow2 shard counts pad with infinity lanes."""
    gX = lax.all_gather(rX, axes)
    gY = lax.all_gather(rY, axes)
    gZ = lax.all_gather(rZ, axes)
    s = gX.shape[0]
    cap = 1 << max(s - 1, 0).bit_length()
    if cap != s:
        pad = ((0, cap - s),) + ((0, 0),) * (gX.ndim - 1)
        gX = jnp.pad(gX, pad)
        gY = jnp.pad(gY, pad)
        gZ = jnp.pad(gZ, pad)
    return _lane_fold(gX, gY, gZ, axis=0)


_SHARDED_FNS: dict[Mesh, object] = {}


def _sharded_fn(mesh: Mesh):
    """Per-mesh jitted shard_map entry (cached: the jit cache then
    dedupes per input shape)."""
    fn = _SHARDED_FNS.get(mesh)
    if fn is not None:
        return fn
    from eth_consensus_specs_tpu.parallel.mesh_ops import BATCH_AXES

    spec = P(None, BATCH_AXES)

    def local(X, Y, Z):
        return _cross_shard_fold(*_lane_fold(X, Y, Z), BATCH_AXES)

    fn = jax.jit(
        shard_map(local, mesh=mesh, in_specs=spec, out_specs=P(), check_vma=False)
    )
    _SHARDED_FNS[mesh] = fn
    return fn


def _clear_sharded_after_fork_in_child() -> None:
    # fork-safety: compiled executables reference the parent's devices
    _SHARDED_FNS.clear()


os.register_at_fork(after_in_child=_clear_sharded_after_fork_in_child)


def g2_many_sum_shape(n_items: int, max_lanes: int, shards: int = 1) -> tuple[int, int]:
    """(item_pad, lane_pad) the batched committee-sum kernel compiles
    at: items pad to pow2 (the item axis replicates across shards),
    lanes to the mesh-aware ragged-committee bucket — ONE shared shape
    model for the ops entry point and the serve layer's compile
    accounting (serve/buckets.agg_lane_bucket), so they can never
    disagree."""
    from eth_consensus_specs_tpu.serve.buckets import agg_lane_bucket, pow2_bucket

    return pow2_bucket(max(n_items, 1)), agg_lane_bucket(max_lanes, shards)


# == host conversion boundary ==============================================


def _points_to_lanes(
    point_lists: list[list], item_pad: int, lane_pad: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    X = np.zeros((item_pad, lane_pad, 2, lz.N_LIMBS), np.uint64)
    Y = np.zeros_like(X)
    Z = np.zeros_like(X)
    one = tw.fq2_to_limbs(Fq2.one())
    for i, points in enumerate(point_lists):
        for j, p in enumerate(points):
            if p.is_infinity():
                continue  # Z stays zero
            X[i, j] = tw.fq2_to_limbs(p.x)
            Y[i, j] = tw.fq2_to_limbs(p.y)
            Z[i, j] = one
    return X, Y, Z


def _jacobian_to_point(X, Y, Z) -> Point:
    z = Fq2(Fq(lz.from_mont_int(Z[0])), Fq(lz.from_mont_int(Z[1])))
    if z == Fq2.zero():
        return g2_infinity()
    x = Fq2(Fq(lz.from_mont_int(X[0])), Fq(lz.from_mont_int(X[1])))
    y = Fq2(Fq(lz.from_mont_int(Y[0])), Fq(lz.from_mont_int(Y[1])))
    zinv = z.inv()
    zinv2 = zinv * zinv
    return Point(x * zinv2, y * zinv2 * zinv, B2)


def sum_g2_many_device(
    point_lists: list[list], mesh: Mesh | None = None, pad_shape: tuple | None = None
) -> list[Point]:
    """Per-committee G2 point sums for many committees in ONE dispatch:
    ``[sum(points) for points in point_lists]``. Ragged lanes pad with
    infinity to the :func:`g2_many_sum_shape` bucket (``pad_shape``
    overrides — the serve layer passes its own bucket so accounting and
    dispatch agree); a multi-device ``mesh`` shards the LANE axis. Each
    result is byte-identical to the host fold
    ``crypto.signature._sum_g2(points)``."""
    n = len(point_lists)
    if n == 0:
        return []
    from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count

    shards = shard_count(mesh)
    if shards <= 1:
        mesh = None
    max_lanes = max((len(p) for p in point_lists), default=1)
    item_pad, lane_pad = pad_shape or g2_many_sum_shape(n, max_lanes, shards)
    assert item_pad >= n and lane_pad >= max_lanes
    X, Y, Z = _points_to_lanes(point_lists, item_pad, lane_pad)
    args = (jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z))
    if mesh is not None:
        obs.count("mesh.dispatches", 1)
        obs.count("mesh.sharded_items", n)
        rX, rY, rZ = _sharded_fn(mesh)(*args)
    else:
        rX, rY, rZ = g2_sum_many_kernel(*args)
    rX, rY, rZ = np.asarray(rX), np.asarray(rY), np.asarray(rZ)
    return [_jacobian_to_point(rX[i], rY[i], rZ[i]) for i in range(n)]


def sum_g2_device(points: list, mesh: Mesh | None = None) -> Point:
    """Device G2 point sum of one committee: ``sum(points)``."""
    return sum_g2_many_device([points], mesh=mesh)[0]
