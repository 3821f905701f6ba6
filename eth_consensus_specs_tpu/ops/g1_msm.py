"""Device G1 multi-scalar multiplication over limb arithmetic.

The hot BLS reductions (aggregate-pubkey sums, KZG commitment MSMs, the
RLC batch-verification combine) are all sum_i k_i * P_i over G1. Here the
whole MSM runs on device: branchless Jacobian point arithmetic (a = 0
short-Weierstrass, infinity encoded as Z = 0, every case handled by
`where` masks so there is no data-dependent control flow), a fixed-window
scalar loop over every (scalar, point) lane at once, then a log2 pairwise
tree reduction — the same shape as the merkle tree reduce, but over
point adds (reference native analogue: arkworks `multiexp_unchecked`
behind utils/bls.py:262-296).

Doubling is dbl-2009-l, addition add-2007-bl with a masked case
analysis, both straight-line code over ops/lazy_limbs (15 x 26-bit
limbs, every bound a Python integer checked at trace time): the field
arithmetic has no loop of its own, so the only loops of a G1 program are
the algorithm's (the scalar loop's table steps and windows, a window's
doublings, the strip scan of the committee sums, the levels of the
tree). A lane first makes its table 0, P, 2P, ... 15P by 14 complete
additions, then walks its scalar in 64 windows of four bits, most
significant first: four doublings and ONE complete addition of the table
entry at the window's digit, chosen a lane by selects; table steps and
windows are trips of one loop, so the program holds one addition body
beside the tree's. A doubling is 7
Montgomery multiplies and a complete addition 23 (16, and 7 more for the
doubling its equal-points lanes take), so a 256-bit scalar is 256 x 7 +
78 x 23 = 3,586 multiplies a lane where a bit-by-bit double-and-add is
256 x 30 = 7,680. The addition stays COMPLETE: scalars run to 2^256,
above the group order, so a window can meet its equal-points case (the
scalar r + 30: the accumulator is 15 P when the table's 15 P is added)
and its opposite-points case (the scalar r). Additions and subtractions
stay lazy inside a formula, with a carry sweep only where a zero test, a
doubled subtrahend or a loop boundary wants normalized limbs, and
conditional subtractions only where a point crosses such a boundary.

Conversion boundary: affine crypto/curve.Point <-> Montgomery limb arrays
on host, 13 x 30-bit limbs (ops/field_limbs) into and out of every jitted
program, which regroups the same 390 bits to 15 x 26 at entry and back at
exit (one radix R = 2^390, so no multiply); the single final
Jacobian->affine inversion also stays host-side (one modular inverse per
MSM, not worth a device Fermat chain yet).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.obs import waterfall
from eth_consensus_specs_tpu.crypto.curve import Point, B1, g1_infinity
from eth_consensus_specs_tpu.crypto.fields import Fq, P as P_INT

from . import lazy_limbs as lz
from .field_limbs import (
    LIMB_BITS as PACK_BITS,
    N_LIMBS,
    ONE_MONT,
    R_INT,
    from_mont_int,
    to_mont,
)
from .lazy_limbs import LF, lf
from .limb_field import LimbField

SCALAR_BITS = 256
# bits a window of the scalar loop (`_scalar_loop`). The program alone on
# one v5e, ms a call at 2 x 32 and at 256 x 32 lanes (PERF.md section 6,
# PR 34; 157.3 and 492.5 a bit at a time): window 4 91.5 and 244.2; window
# 5 87.4 and 241.5, for a table of 32 rows and 3,706 multiplies; the entry
# taken by a gather along the table axis in place of the selects 82.2 and
# 265.9, by one masked reduce over the table axis 91.9 and 242.0. With the
# table built by a loop of its own (a second addition body): four copies
# of the doubling in place of their loop 88.7 against 94.8 and 247.2
# against 244.5, for 25 and 80 s more of compile. Window 5's 4.5 % and
# 1.1 % cost a table twice the size (94 MB at 8,192 lanes) and a first
# window padded to its width: not taken, and open (PERF.md section 7)
WINDOW_BITS = 4
WINDOWS = SCALAR_BITS // WINDOW_BITS
TABLE = 1 << WINDOW_BITS
# Montgomery multiplies of `_dbl`, and of `_add` with the doubling its
# equal-points lanes take
DBL_MULS = 7
ADD_MULS = 16 + DBL_MULS
# what one execution of the scalar loop is from its static shape: the
# sequential trips (table steps, then windows) and the multiplies a lane
SCALAR_STEPS = TABLE - 2 + WINDOWS
SCALAR_FIELD_MULS = SCALAR_BITS * DBL_MULS + SCALAR_STEPS * ADD_MULS

assert lz.R_INT == R_INT  # one Montgomery radix: the two limb forms hold the same integer


def _regroup(a, bits_in: int, bits_out: int, n_out: int):
    """The same integer in limbs of another width: u64[..., n] limbs of
    `bits_in` bits (each below 2^bits_in) to `n_out` limbs of `bits_out`
    bits, by static shifts and masks. Bits past the last input limb
    read as zero, so the value is kept wherever it fits `n_out` limbs."""
    n_in = a.shape[-1]
    mask = jnp.uint64((1 << bits_out) - 1)
    out = []
    for j in range(n_out):
        i, off = divmod(j * bits_out, bits_in)
        limb = a[..., i] >> jnp.uint64(off)
        have = bits_in - off
        while have < bits_out and i + 1 < n_in:
            i += 1
            limb = limb | (a[..., i] << jnp.uint64(have))
            have += bits_in
        out.append(limb & mask)
    return jnp.stack(out, axis=-1)


def _to_lazy(a):
    """A program's input, 13 x 30-bit Montgomery limbs in [0, 2p), as the
    15 x 26-bit limbs the arithmetic runs on (canonical for `lazy_limbs`:
    limbs < 2^26, value < 2p)."""
    return _regroup(a, PACK_BITS, lz.LIMB_BITS, lz.N_LIMBS)


def _from_lazy(a):
    """Canonical 15 x 26-bit limbs back to the 13 x 30-bit limbs every
    program returns (a value < 2p < 2^382 fits them)."""
    return _regroup(a, lz.LIMB_BITS, PACK_BITS, N_LIMBS)


def _to_lazy_point(X, Y, Z):
    return _to_lazy(X), _to_lazy(Y), _to_lazy(Z)


def _from_lazy_point(point):
    X, Y, Z = point
    return _from_lazy(X), _from_lazy(Y), _from_lazy(Z)


# A point here is a tuple (X, Y, Z) of `LF`s. Inside a formula values stay
# lazy, and a doubling is folded into a multiply's operand (2a * b for
# 2ab): a product comes out canonical for free where a sum would need a
# carry sweep and conditional subtractions. A point is made canonical
# (`_canon`: limbs < 2^26, values < 2p) only where it leaves the traced
# formulas as raw arrays: a loop carry, a program's result.


def _wrap(X, Y, Z):
    """Canonical limb arrays as a point."""
    return lf(X), lf(Y), lf(Z)


def _canon(point):
    """A point's canonical limb arrays, so that `_wrap` on the other side
    of a loop boundary tells the truth about them."""
    return tuple(lz.shrink(c).v for c in point)


def _dbl(point):
    """dbl-2009-l (a=0). Infinity (Z=0) and Y=0 both yield Z3=0."""
    X, Y, Z = point
    A = lz.mul(X, X)
    B = lz.mul(Y, Y)
    # D = 2*((X+B)^2 - A - C) is 4XB, and 8C is 8B^2
    D = lz.mul(lz.dbl(X), lz.dbl(B))
    C8 = lz.mul(lz.dbl(lz.dbl(B)), lz.dbl(B))
    E = lz.add(lz.dbl(A), A)  # 3A
    F = lz.mul(E, E)
    X3 = lz.sub(lz.sub(F, D), D)
    Y3 = lz.sub(lz.mul(E, lz.sub(D, X3)), C8)
    Z3 = lz.mul(lz.dbl(Y), Z)
    return X3, Y3, Z3


def _select(mask, a: LF, b: LF) -> LF:
    """Per-lane select between two elements: mask ? a : b, under the
    weaker of their bounds."""
    return LF(jnp.where(mask[..., None], a.v, b.v), max(a.max, b.max), max(a.val, b.val))


def _select_point(mask, p, q):
    return tuple(_select(mask, a, b) for a, b in zip(p, q))


def _add(p, q):
    """Complete Jacobian add via masked case analysis (add-2007-bl core)."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    Z1Z1 = lz.mul(Z1, Z1)
    Z2Z2 = lz.mul(Z2, Z2)
    U1 = lz.mul(X1, Z2Z2)
    U2 = lz.mul(X2, Z1Z1)
    S1 = lz.mul(lz.mul(Y1, Z2), Z2Z2)
    S2 = lz.mul(lz.mul(Y2, Z1), Z1Z1)
    H = lz.sub(U2, U1)
    rr = lz.sub(S2, S1)
    r2 = lz.dbl(rr)
    HH = lz.dbl(H)
    I = lz.mul(HH, HH)
    J = lz.mul(H, I)
    V2 = lz.mul(U1, lz.dbl(I))  # 2V
    # swept here, since 2*X3 is subtracted below: a subtrahend's limbs
    # set the multiple of p that covers them
    X3 = lz.norm(lz.sub(lz.sub(lz.mul(r2, r2), J), V2))
    SJ2 = lz.mul(lz.dbl(S1), J)  # 2*S1*J
    # r2*(V - X3) as rr*(2V - 2*X3)
    Y3 = lz.sub(lz.mul(rr, lz.sub(V2, lz.dbl(X3))), SJ2)
    # (Z1+Z2)^2 - Z1Z1 - Z2Z2 is 2*Z1*Z2
    Z3 = lz.mul(lz.mul(lz.dbl(Z1), Z2), H)

    same_x = lz.is_zero(H)
    same_y = lz.is_zero(rr)
    # default: generic add; same point: double; opposite points: infinity
    opposite = same_x & ~same_y
    out = (X3, Y3, _select(opposite, lz.zero_like(Z3), Z3))
    out = _select_point(same_x & same_y, _dbl(p), out)
    # either input at infinity: pass the other through
    out = _select_point(lz.is_zero(Z2), p, out)
    return _select_point(lz.is_zero(Z1), q, out)


def _table_entries(tables, digit):
    """The point tables[digit] a lane, by selects over the TABLE entries:
    a per-lane choice with no gather and no data-dependent control flow."""
    entry = _wrap(*(c[0] for c in tables))
    for d in range(1, TABLE):
        entry = _select_point(digit == d, _wrap(*(c[d] for c in tables)), entry)
    return entry


def _scalar_loop(bits, X, Y, Z):
    """Fixed-window scalar multiplication of every lane: MSB-first `bits`
    u64[..., 256] times the points u64[..., 15] a coordinate, every op
    broadcasting across the leading axes. ONE loop of SCALAR_STEPS trips
    whose step is "k doublings, add an operand, store", so that the
    program holds ONE doubling body and ONE complete addition body (a
    second addition body was 5 MB of code and 25 s of every process's
    set-up: PERF.md section 6, PR 34):

    * TABLE - 2 table steps (k = 0, the operand the lane's point P): the
      running point walks P, 2P (the addition's equal-points case), ...
      15P, each stored as its table entry; entry 0 is infinity (Z = 0),
      and a padded lane (Z = 0) gets TABLE infinities;
    * WINDOWS window steps, most significant first (k = WINDOW_BITS trips
      of the doubling body, the operand each lane's table entry at its
      window's digit, made here from the window's bits); the first starts
      from infinity, and a window's sum goes to a spare last table row
      that nothing reads.

    Takes canonical limb arrays and returns the products and the tables
    (u64[TABLE + 1, ..., 15] a coordinate) as such; the running point
    crosses every loop boundary canonical."""
    base = _wrap(X, Y, Z)
    weights = jnp.asarray([1 << k for k in reversed(range(WINDOW_BITS))], jnp.uint64)
    digits = (bits.reshape(*bits.shape[:-1], WINDOWS, WINDOW_BITS) * weights).sum(axis=-1)

    def double(_, acc):
        return _canon(_dbl(_wrap(*acc)))

    def step(t, carry):
        acc, tables = carry
        window = t - jnp.int32(TABLE - 2)  # negative while the table is built
        building = window < 0
        # the table's steps leave 15P behind: the windows start from infinity
        acc = (acc[0], acc[1], jnp.where(window == 0, jnp.uint64(0), acc[2]))
        # i32 loop bounds: python-int bounds widen the counter to i64
        # under the package-wide x64 flag (jaxlint x64-drift)
        doublings = jnp.where(building, jnp.int32(0), jnp.int32(WINDOW_BITS))
        acc = lax.fori_loop(jnp.int32(0), doublings, double, acc)
        digit = lax.dynamic_index_in_dim(digits, jnp.maximum(window, 0), axis=-1, keepdims=False)
        operand = _select_point(building, base, _table_entries(tables, digit))
        acc = _canon(_add(_wrap(*acc), operand))
        row = jnp.where(building, t + 2, jnp.int32(TABLE))
        tables = tuple(
            lax.dynamic_update_index_in_dim(c, a, row, 0) for c, a in zip(tables, acc)
        )
        return acc, tables

    def rows(c):  # infinity, P, and the rows the table steps fill
        return jnp.concatenate(
            [jnp.zeros_like(c)[None], c[None], jnp.zeros((TABLE - 1, *c.shape), c.dtype)]
        )

    start = (X, Y, Z), tuple(rows(c) for c in (X, Y, Z))
    return lax.fori_loop(jnp.int32(0), jnp.int32(SCALAR_STEPS), step, start)


def _tree_sum(mX, mY, mZ):
    """Pairwise point-sum of N (power-of-two) Jacobian lanes, canonical
    limb arrays in and out. The tree's levels are passes of ONE add body
    over N/2 lanes: a level of `width` live lanes adds lane i + width/2
    onto lane i, and what the lanes from width/2 up then hold nothing
    reads. An add unrolled a level was most of a program's code."""
    n = mX.shape[0]
    if n == 1:
        return mX[0], mY[0], mZ[0]
    half = n // 2

    def level(lanes, width):
        low = _wrap(*(c[:half] for c in lanes))
        high = _wrap(*(lax.dynamic_slice_in_dim(c, width // 2, half) for c in lanes))
        sums = _canon(_add(low, high))
        return tuple(jnp.concatenate([s, c[half:]]) for s, c in zip(sums, lanes)), None

    widths = np.array([n >> k for k in range(n.bit_length() - 1)], np.int32)
    lanes, _ = lax.scan(level, (mX, mY, mZ), widths)
    return tuple(c[0] for c in lanes)


def _msm_lanes(bits, X, Y, Z):
    """The MSM of one item's lanes ([L, ...] arrays) or of each item of a
    batch ([I, L, ...]): the windowed scalar loop over every lane at
    once, then a pairwise tree reduce an item: the shared body of
    msm_kernel, msm_many_kernel and their sharded forms."""
    tree = _tree_sum if X.ndim == 2 else jax.vmap(_tree_sum)
    return tree(*_scalar_loop(bits, X, Y, Z)[0])


@jax.jit
def msm_kernel(bits, X, Y, Z):
    """MSM over N (power-of-two) lanes: bits u64[N,256], X/Y/Z u64[N,13]
    (Montgomery). Returns Jacobian (X,Y,Z) u64[13] of sum_i k_i * P_i."""
    return _from_lazy_point(_msm_lanes(bits, *_to_lazy_point(X, Y, Z)))


@jax.jit
def sum_kernel(X, Y, Z):
    """Plain point sum over N (power-of-two) lanes — the unit-scalar MSM
    without the scalar loop (aggregate-pubkey fast path)."""
    return _from_lazy_point(_tree_sum(*_to_lazy_point(X, Y, Z)))


@jax.jit
def sum_many_kernel(X, Y, Z):
    """Per-item point sums over [I, L, 13] lane arrays (L a power of
    two): the batched aggregate-pubkey kernel — one dispatch sums every
    committee of a flush instead of one dispatch per item."""
    return _from_lazy_point(jax.vmap(_tree_sum)(*_to_lazy_point(X, Y, Z)))


def _strip_sum(X, Y, Z, strip: int):
    """Point sum of L (power-of-two) Jacobian lanes, `strip` lanes at a
    time: the strips accumulate into `strip` running sums through ONE add
    body in a scan, and a pairwise tree folds those. Exact group math, so
    the affine result is the tree's whatever the strip; `strip` = L is
    the tree alone."""
    lanes = X.shape[0]
    if strip >= lanes:
        return _tree_sum(X, Y, Z)
    parts = [a.reshape(lanes // strip, strip, lz.N_LIMBS) for a in (X, Y, Z)]

    def step(acc, part):
        return _canon(_add(_wrap(*acc), _wrap(*part))), None

    acc, _ = lax.scan(step, tuple(a[0] for a in parts), tuple(a[1:] for a in parts))
    return _tree_sum(*acc)


def _sum_indexed(table_x, table_y, index, strip: int):
    live = index >= 0
    at = jnp.where(live, index, 0)
    Z = jnp.where(live[..., None], jnp.asarray(lz.ONE_MONT), jnp.uint64(0))
    sums = jax.vmap(partial(_strip_sum, strip=strip))(
        _to_lazy(table_x[at]), _to_lazy(table_y[at]), Z
    )
    return _from_lazy_point(sums)


@partial(jax.jit, static_argnames="strip")
def sum_indexed_kernel(table_x, table_y, index, strip: int):
    """Per-item sums of registry keys that live on the device: the
    table's affine Montgomery coordinates u64[N, 13], `index` i32[I, L]
    (L a power of two, a negative entry an empty lane). The flush sends
    the indices; the lanes are gathered here, so no point is packed on
    the host. Returns Jacobian u64[I, 13] per coordinate."""
    return _sum_indexed(table_x, table_y, index, strip)


@jax.jit
def msm_many_kernel(bits, X, Y, Z):
    """Per-item full-scalar MSMs over [I, L, ...] lane arrays (L a power
    of two): bits u64[I, L, 256], X/Y/Z u64[I, L, 13]. Returns Jacobian
    u64[I, 13] per coordinate — item i is sum_j bits[i,j] * P[i,j].

    This is the KZG batch-verification fold: one flush's RLC combine
    needs TWO independent MSMs (the proof lincomb and the commitment-
    minus-y + proof-z lincomb) and this kernel runs both in ONE
    dispatch instead of two msm_kernel round-trips."""
    return _from_lazy_point(_msm_lanes(bits, *_to_lazy_point(X, Y, Z)))


# == mesh-sharded kernels ==================================================
#
# Two shard axes, matching the two hot call patterns:
#   * ITEM axis (sum_g1_many_device): the RLC batch's per-item committee
#     sums are independent — shard items, no collectives;
#   * LANE axis (msm_g1_device): one big MSM splits its (scalar, point)
#     lanes — each shard tree-sums its lanes, then a cross-shard Jacobian
#     reduction (all_gather of the 3x13-limb partials + the same pairwise
#     tree) combines them. Jacobian addition is exact group math and the
#     final affine conversion is canonical, so any shard count returns
#     byte-identical points.


def _cross_shard_tree_sum(point, axes):
    """all_gather per-shard Jacobian partials (canonical lazy limbs here,
    [..., 13] each as gathered and as returned) and tree-sum them over
    the gathered shard axis; non-pow2 shard counts pad with infinity
    lanes (Z = 0)."""
    gX, gY, gZ = (lax.all_gather(a, axes) for a in _from_lazy_point(point))
    s = gX.shape[0]
    cap = 1 << max(s - 1, 0).bit_length()
    if cap != s:
        pad = ((0, cap - s),) + ((0, 0),) * (gX.ndim - 1)
        gX = jnp.pad(gX, pad)
        gY = jnp.pad(gY, pad)
        gZ = jnp.pad(gZ, pad)
    return _from_lazy_point(_tree_sum(*_to_lazy_point(gX, gY, gZ)))


_SHARDED_FNS: dict[tuple, object] = {}


def _sharded_fn(mesh: Mesh, kind: str):
    """Per-(mesh, kernel) jitted shard_map entry (cached: the jit cache
    then dedupes per input shape)."""
    key = (mesh, kind)
    fn = _SHARDED_FNS.get(key)
    if fn is not None:
        return fn
    from eth_consensus_specs_tpu.parallel.mesh_ops import BATCH_AXES

    spec = P(BATCH_AXES)
    if kind == "msm":

        def local(bits, X, Y, Z):
            return _cross_shard_tree_sum(
                _msm_lanes(bits, *_to_lazy_point(X, Y, Z)), BATCH_AXES
            )

        fn = jax.jit(
            shard_map(local, mesh=mesh, in_specs=spec, out_specs=P(), check_vma=False)
        )
    elif kind == "sum":

        def local(X, Y, Z):
            return _cross_shard_tree_sum(_tree_sum(*_to_lazy_point(X, Y, Z)), BATCH_AXES)

        fn = jax.jit(
            shard_map(local, mesh=mesh, in_specs=spec, out_specs=P(), check_vma=False)
        )
    elif kind == "msm_many":
        # per-item MSMs with the LANE axis (axis 1) sharded: each shard
        # runs the scalar loop + tree-sums its lane slice of every item, then
        # ONE gather combines the [I, 13] partials — the per-item sums
        # ride the same cross-shard Jacobian reduce as the single MSM,
        # so results are byte-identical at any shard count
        lane_spec = P(None, BATCH_AXES)

        def local(bits, X, Y, Z):
            return _cross_shard_tree_sum(
                _msm_lanes(bits, *_to_lazy_point(X, Y, Z)), BATCH_AXES
            )

        fn = jax.jit(
            shard_map(
                local, mesh=mesh, in_specs=lane_spec, out_specs=P(),
                check_vma=False,
            )
        )
    elif kind == "sum_indexed":
        # the key table replicated, the index array's item axis sharded:
        # each shard gathers and sums its own items, no collectives

        def local(table_x, table_y, index):
            return _sum_indexed(table_x, table_y, index, KEY_SUM_STRIP)

        fn = jax.jit(
            shard_map(
                local, mesh=mesh, in_specs=(P(), P(), spec), out_specs=spec,
                check_vma=False,
            )
        )
    else:  # "sum_many": item axis sharded, no collectives

        def local(X, Y, Z):
            return _from_lazy_point(jax.vmap(_tree_sum)(*_to_lazy_point(X, Y, Z)))

        fn = jax.jit(
            shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
        )
    _SHARDED_FNS[key] = fn
    return fn


def _clear_sharded_after_fork_in_child() -> None:
    # fork-safety: compiled executables reference the parent's devices
    _SHARDED_FNS.clear()


os.register_at_fork(after_in_child=_clear_sharded_after_fork_in_child)


def mesh_lane_pad(n: int, shards: int) -> int:
    """Lane padding target under `shards`: per-shard lane counts padded
    to a power of two (the per-shard tree reduce needs pow2), total =
    shards * per-shard. For pow2 shard counts this equals the global
    pow2; for non-pow2 meshes it pads strictly less."""
    if shards <= 1:
        n = max(n, 1)
        return 1 << max(n - 1, 0).bit_length()
    per = -(-n // shards)
    per = max(per, 1)
    return shards * (1 << max(per - 1, 0).bit_length())


def many_sum_shape(n_items: int, max_lanes: int, shards: int = 1) -> tuple[int, int]:
    """(item_pad, lane_pad) the batched per-item sum kernel compiles at:
    items pad to per-shard pow2 (x shards), lanes to global pow2 — ONE
    shared shape model for the ops entry point and the serve layer's
    compile accounting, so they can never disagree."""
    return mesh_lane_pad(n_items, shards), mesh_lane_pad(max_lanes, 1)


# == host conversion boundary ==============================================


# the host's array forms of int <-> 13 x 30-bit limbs (no device op of it is used)
_FQ_LIMBS = LimbField(P_INT)


def _points_to_limbs(points: list) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Affine points to Montgomery limbs u64[n, 13] a coordinate, Z = 0
    for infinity: one multiply an integer, the limbs cut from them all by
    array operations."""
    n = len(points)
    X = np.zeros((n, N_LIMBS), np.uint64)
    Y = np.zeros((n, N_LIMBS), np.uint64)
    Z = np.zeros((n, N_LIMBS), np.uint64)
    live = [i for i, p in enumerate(points) if not p.is_infinity()]
    if live:
        coords = [points[i].x.n * R_INT % P_INT for i in live]
        coords += [points[i].y.n * R_INT % P_INT for i in live]
        limbs = _FQ_LIMBS.ints_to_limbs_batch(coords)
        X[live], Y[live], Z[live] = limbs[: len(live)], limbs[len(live) :], ONE_MONT
    return X, Y, Z


def _scalars_to_bits(scalars: list[int]) -> np.ndarray:
    """Scalars in [0, 2^256) to their bits, most significant first,
    u64[n, 256]; ``to_bytes`` raises OverflowError outside that range."""
    buf = b"".join([int(k).to_bytes(SCALAR_BITS // 8, "big") for k in scalars])
    octets = np.frombuffer(buf, np.uint8).reshape(len(scalars), SCALAR_BITS // 8)
    return np.unpackbits(octets, axis=1).astype(np.uint64)


def _jacobian_to_points(X, Y, Z) -> list[Point]:
    """Rows of Jacobian Montgomery limbs u64[n, 13] to affine points, the
    rows' field inversions batched into one (a flush of committee sums
    has 128)."""
    xs, ys, zs = ([from_mont_int(row) for row in np.asarray(a)] for a in (X, Y, Z))
    live = [i for i, z in enumerate(zs) if z]
    prefix, acc = [], 1
    for i in live:
        prefix.append(acc)
        acc = acc * zs[i] % P_INT
    inv = pow(acc, -1, P_INT)
    points = [g1_infinity()] * len(zs)
    for i, before in zip(reversed(live), reversed(prefix)):
        zinv = inv * before % P_INT
        inv = inv * zs[i] % P_INT
        zinv2 = zinv * zinv % P_INT
        points[i] = Point(Fq(xs[i] * zinv2 % P_INT), Fq(ys[i] * zinv2 % P_INT * zinv % P_INT), B1)
    return points


def _jacobian_to_point(X, Y, Z) -> Point:
    return _jacobian_to_points(*(np.asarray(a)[None] for a in (X, Y, Z)))[0]


def _pad_lanes(arrs, n: int, cap: int):
    """Pad lane arrays to exactly `cap` lanes with infinity lanes (Z = 0,
    zero scalars)."""
    if cap == n:
        return arrs
    return [
        np.concatenate([a, np.zeros((cap - n,) + a.shape[1:], a.dtype)]) for a in arrs
    ]


def _count_scalar_loop() -> None:
    """One execution of the scalar loop, from its static shape: the
    sequential depth and the work show without a trace."""
    obs.count("g1_msm.scalar_steps", SCALAR_STEPS)
    obs.count("g1_msm.field_muls", SCALAR_FIELD_MULS)


def msm_g1_device(points: list, scalars: list[int], mesh: Mesh | None = None) -> Point:
    """Device MSM entry: sum_i scalars[i] * points[i] over G1. With a
    multi-device `mesh` the lanes shard over it (per-shard scalar loop
    + local tree sum, then the cross-shard Jacobian reduction) — the
    affine result is byte-identical to the single-device dispatch."""
    assert len(points) == len(scalars)
    if not points:
        return g1_infinity()
    from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count

    shards = shard_count(mesh)
    if shards <= 1:
        mesh = None
    X, Y, Z = _points_to_limbs(points)
    cap = mesh_lane_pad(len(points), shards)
    if all(int(k) == 1 for k in scalars):
        # aggregate-pubkey fast path: tree sum only, no scalar loop
        X, Y, Z = _pad_lanes([X, Y, Z], len(points), cap)
        args = (jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z))
        if mesh is not None:
            obs.count("mesh.dispatches", 1)
            obs.count("mesh.sharded_items", len(points))
            rX, rY, rZ = _sharded_fn(mesh, "sum")(*args)
        else:
            rX, rY, rZ = sum_kernel(*args)
    else:
        bits = _scalars_to_bits(scalars)
        bits, X, Y, Z = _pad_lanes([bits, X, Y, Z], len(points), cap)
        args = (jnp.asarray(bits), jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z))
        _count_scalar_loop()
        if mesh is not None:
            obs.count("mesh.dispatches", 1)
            obs.count("mesh.sharded_items", len(points))
            rX, rY, rZ = _sharded_fn(mesh, "msm")(*args)
        else:
            rX, rY, rZ = msm_kernel(*args)
    return _jacobian_to_point(np.asarray(rX), np.asarray(rY), np.asarray(rZ))


def sum_g1_device(points: list, mesh: Mesh | None = None) -> Point:
    """Device point sum (unit-scalar MSM): sum_i points[i]."""
    return msm_g1_device(points, [1] * len(points), mesh=mesh)


def msm_g1_many_device(
    point_lists: list[list],
    scalar_lists: list[list[int]],
    mesh: Mesh | None = None,
    pad_shape: tuple | None = None,
) -> list[Point]:
    """Independent full-scalar MSMs for many items in ONE dispatch:
    ``[msm_g1(points, scalars) for ...]`` — the KZG RLC fold's seam.
    Lanes pad to the pow2 of the widest item (``pad_shape`` overrides:
    the serve layer passes its bucket so accounting and dispatch
    agree); a multi-device `mesh` shards the LANE axis with the
    cross-shard Jacobian combine, byte-identical to single-device.
    Each result equals ``msm_g1_device(points, scalars)``."""
    n = len(point_lists)
    assert n == len(scalar_lists)
    if n == 0:
        return []
    from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count

    shards = shard_count(mesh)
    if shards <= 1:
        mesh = None
        shards = 1
    max_lanes = max(len(p) for p in point_lists)
    item_pad, lane_pad = pad_shape or (n, mesh_lane_pad(max_lanes, shards))
    assert item_pad >= n and lane_pad >= max_lanes
    with waterfall.leg("g1_msm.pack"):
        bits = np.zeros((item_pad, lane_pad, SCALAR_BITS), np.uint64)
        X = np.zeros((item_pad, lane_pad, N_LIMBS), np.uint64)
        Y = np.zeros((item_pad, lane_pad, N_LIMBS), np.uint64)
        Z = np.zeros((item_pad, lane_pad, N_LIMBS), np.uint64)
        # every item's lanes converted together, then laid at (item, lane)
        lengths = [len(points) for points in point_lists]
        assert lengths == [len(scalars) for scalars in scalar_lists]
        if sum(lengths):
            item = np.repeat(np.arange(n), lengths)
            lane = np.concatenate([np.arange(k) for k in lengths])
            X[item, lane], Y[item, lane], Z[item, lane] = _points_to_limbs(
                [p for points in point_lists for p in points]
            )
            bits[item, lane] = _scalars_to_bits([s for scalars in scalar_lists for s in scalars])
        args = (jnp.asarray(bits), jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z))
    _count_scalar_loop()
    # host clock round a synced device call: launch, the MSM program,
    # transfer out
    with waterfall.leg("g1_msm.call"):
        if mesh is not None:
            obs.count("mesh.dispatches", 1)
            obs.count("mesh.sharded_items", n)
            rX, rY, rZ = _sharded_fn(mesh, "msm_many")(*args)
        else:
            rX, rY, rZ = msm_many_kernel(*args)
        rX, rY, rZ = np.asarray(rX), np.asarray(rY), np.asarray(rZ)
    with waterfall.leg("g1_msm.unpack"):
        return _jacobian_to_points(rX[:n], rY[:n], rZ[:n])


def sum_g1_many_device(
    point_lists: list[list], mesh: Mesh | None = None, pad_shape: tuple | None = None
) -> list[Point]:
    """Per-item point sums for many committees in ONE dispatch:
    ``[sum(points) for points in point_lists]``. Lanes pad to the pow2 of
    the largest committee, items to the :func:`many_sum_shape` bucket
    (``pad_shape`` overrides — the serve layer passes its own bucket so
    accounting and dispatch agree); a multi-device `mesh` shards the item
    axis. Each result is byte-identical to ``sum_g1_device(points)``."""
    n = len(point_lists)
    if n == 0:
        return []
    from eth_consensus_specs_tpu.parallel.mesh_ops import shard_count

    shards = shard_count(mesh)
    if shards <= 1:
        mesh = None
    max_lanes = max(len(p) for p in point_lists)
    item_pad, lane_pad = pad_shape or many_sum_shape(n, max_lanes, shards)
    assert item_pad >= n and lane_pad >= max_lanes
    X = np.zeros((item_pad, lane_pad, N_LIMBS), np.uint64)
    Y = np.zeros((item_pad, lane_pad, N_LIMBS), np.uint64)
    Z = np.zeros((item_pad, lane_pad, N_LIMBS), np.uint64)
    one = to_mont(1)
    for i, points in enumerate(point_lists):
        for j, p in enumerate(points):
            if p.is_infinity():
                continue  # Z stays zero
            X[i, j] = to_mont(p.x.n)
            Y[i, j] = to_mont(p.y.n)
            Z[i, j] = one
    args = (jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z))
    if mesh is not None:
        obs.count("mesh.dispatches", 1)
        obs.count("mesh.sharded_items", n)
        rX, rY, rZ = _sharded_fn(mesh, "sum_many")(*args)
    else:
        rX, rY, rZ = sum_many_kernel(*args)
    rX, rY, rZ = np.asarray(rX), np.asarray(rY), np.asarray(rZ)
    return [_jacobian_to_point(rX[i], rY[i], rZ[i]) for i in range(n)]


# lanes a strip of sum_indexed_kernel. At 128 x 512 on one v5e (PERF.md
# section 5): strip 4 443 ms, 16 127 ms, 64 68 ms a flush, and 68 s,
# 159 s, 206 s to compile; the C core sums the same keys in 105 ms
KEY_SUM_STRIP = 64


def sum_indexed_device(table_limbs, rows: list, pad_shape: tuple, mesh: Mesh | None = None):
    """Per-item sums of registry keys resident on the device
    (``key_table.KeyTable.device_limbs``): ``rows`` are the items' registry
    indices, padded here to ``pad_shape`` (items, lanes: the
    :func:`many_sum_shape` bucket); a multi-device `mesh`, on which the
    table lies replicated, shards the item axis. Returns the Jacobian sums
    on the host, u64[items, 13] a coordinate (``_jacobian_to_points``
    makes points of them)."""
    index = np.full(pad_shape, -1, np.int32)
    for i, row in enumerate(rows):
        index[i, : len(row)] = row
    if mesh is not None:
        obs.count("mesh.dispatches", 1)
        obs.count("mesh.sharded_items", len(rows))
        out = _sharded_fn(mesh, "sum_indexed")(*table_limbs, jnp.asarray(index))
    else:
        out = sum_indexed_kernel(*table_limbs, jnp.asarray(index), strip=KEY_SUM_STRIP)
    return tuple(np.asarray(a)[: len(rows)] for a in out)
