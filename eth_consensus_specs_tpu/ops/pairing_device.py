"""Batched optimal-ate pairing on the device (BLS12-381).

TPU-first split of the pairing:

* **Host "preparation"** (`prepare_g2`): the Miller loop's G2 side — the
  tangent/chord slopes and the T-point walk — depends ONLY on Q and the
  fixed BLS parameter, so the 69 affine steps run once per distinct G2
  point on host (tiny Fq2 work) and produce per-step line coefficients.
  This is the same factoring arkworks/blst call "G2Prepared"; here it is
  also the device seam.
* **Device accumulation** (`miller_from_coeffs`): the heavy part — 63
  Fq12 squarings and ~69 sparse line multiplications per pair — runs as
  ONE fixed-shape lax.scan, vmapped over all pairs of a batch in lanes.
  No inversions, no control flow, no G2 arithmetic on device.
* **Device final exponentiation**: fast cyclotomic membership check for
  `pairing_check` (5 powx scans; computes f^(3*hard) exactly like the C
  core, native/bls12_381.c:1128-1152) and the exact hard part for GT
  export parity.

Line model (identical to the host oracle and the C core, so Miller values
match crypto/pairing.py BIT-FOR-BIT): untwisted line through T, Q
evaluated at P = (px, py) is the sparse Fq12 element

    l = py + (lam*tx - ty) xi^-1 w^3 - lam*px xi^-1 w^5

with only the (lam*tx - ty)*xi^-1 and lam*xi^-1 factors precomputed on
host (Q-only data); the -px multiply happens on device.

Preconditions: G2 inputs must be in the prime-order subgroup (enforced by
crypto/curve.g2_from_bytes) — then T never meets ±Q mid-loop and no
vertical lines occur (prepare_g2 asserts this). Infinity on either side
is handled with an active-mask (e(P, O) = e(O, Q) = 1).

Reference parity surface: utils/bls.py:224-296 `pairing_check` — the one
native call every reference verification funnels into.
"""

from __future__ import annotations

import os

import numpy as np

import eth_consensus_specs_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp
from jax import lax

from eth_consensus_specs_tpu.crypto.fields import (
    BLS_X,
    P as P_INT,
    R as R_ORDER,
    XI,
    Fq12,
)
from eth_consensus_specs_tpu.ops import fq12_tower as tw
from eth_consensus_specs_tpu.ops import lazy_limbs as lz
from eth_consensus_specs_tpu.ops.lazy_limbs import LF, lf

N_LIMBS = lz.N_LIMBS
_XI_INV = XI.inv()
_BLS_X_ABS = -BLS_X

# Fixed step schedule: one row per Miller step; True rows square f first
# (doubling steps), False rows are the addition steps after set bits.
_SCHEDULE: list[bool] = []
for _bit in range(62, -1, -1):
    _SCHEDULE.append(True)
    if (_BLS_X_ABS >> _bit) & 1:
        _SCHEDULE.append(False)
N_STEPS = len(_SCHEDULE)
_SQR_FLAGS = np.array(_SCHEDULE, np.uint8)


# ----------------------------------------------------------- host prepare --


def prepare_g2(q) -> np.ndarray:
    """Per-step line coefficients for a (subgroup, non-infinity) G2 point:
    [N_STEPS, 2, 2, 15] Montgomery limbs of (a3, lam_xi) per step, where
    a3 = (lam*tx - ty)*xi^-1 and lam_xi = lam*xi^-1."""
    assert not q.is_infinity(), "prepare_g2: infinity handled by caller mask"
    rows = np.zeros((N_STEPS, 2, 2, N_LIMBS), np.uint64)
    t_x, t_y = q.x, q.y
    step = 0
    for bit in range(62, -1, -1):
        # doubling: tangent at T
        x_sq = t_x.square()
        lam = (x_sq + x_sq + x_sq) * (t_y + t_y).inv()
        rows[step, 0] = tw.fq2_to_limbs((lam * t_x - t_y) * _XI_INV)
        rows[step, 1] = tw.fq2_to_limbs(lam * _XI_INV)
        x3 = lam.square() - t_x - t_x
        t_y = lam * (t_x - x3) - t_y
        t_x = x3
        step += 1
        if (_BLS_X_ABS >> bit) & 1:
            # addition: chord through T and Q (never vertical for
            # subgroup Q: T = kQ with k != +-1 mod r at every add step)
            assert t_x != q.x, "vertical line in ate loop — Q not in subgroup?"
            lam = (q.y - t_y) * (q.x - t_x).inv()
            rows[step, 0] = tw.fq2_to_limbs((lam * t_x - t_y) * _XI_INV)
            rows[step, 1] = tw.fq2_to_limbs(lam * _XI_INV)
            x3 = lam.square() - t_x - q.x
            t_y = lam * (t_x - x3) - t_y
            t_x = x3
            step += 1
    assert step == N_STEPS
    return rows


def g1_affine_limbs(p) -> tuple[np.ndarray, np.ndarray]:
    """(px, py) Montgomery limbs of a non-infinity G1 point."""
    return lz.to_mont(p.x.n), lz.to_mont(p.y.n)


# ---------------------------------------------------------- device miller --


def _fq12_mul_line(f: LF, py: LF, a3: LF, a5: LF) -> LF:
    """f *= (py + a3 w^3 + a5 w^5), sparse (mirrors native fp12_mul_line).

    l.c0 = (py, 0, 0); l.c1 = (0, a3, a5). For an Fq6 half (s0, s1, s2):
    (s0,s1,s2)*(0,a3,a5) = (xi(s1 a5 + s2 a3), s0 a3 + xi s2 a5,
    s0 a5 + s1 a3). All twelve sparse Fq2 products across BOTH halves ride
    one stacked fq2_mul; the twelve py*Fq products ride one stacked mont."""
    f0, f1 = tw._part(f, 0, 3), tw._part(f, 1, 3)

    def lanes(src: LF):
        s0, s1, s2 = (tw._part(src, i, 2) for i in range(3))
        return [s1, s2, s0, s2, s0, s1], [a5, a3, a3, a5, a5, a3]

    l0, r0 = lanes(f0)
    l1, r1 = lanes(f1)
    prods = tw._unstack(
        tw.fq2_mul(tw._lane_stack(l0 + l1), tw._lane_stack(r0 + r1)), 12
    )

    def sparse6(p: list[LF]) -> LF:
        c0 = tw.fq2_mul_xi(lz.add(p[0], p[1]))
        c1 = lz.add(p[2], tw.fq2_mul_xi(p[3]))
        c2 = lz.add(p[4], p[5])
        return tw._stack([c0, c1, c2], axis=-3)

    sp0 = sparse6(prods[:6])
    sp1 = sparse6(prods[6:])

    # py * f as one 12-lane mont instance over the flattened Fq components
    comps = [
        LF(f.v[..., h, v, u, :], f.max, f.val)
        for h in range(2)
        for v in range(3)
        for u in range(2)
    ]
    scaled = tw._unstack(lz.mul(tw._lane_stack(comps), tw._lane_stack([py] * 12)), 12)

    def pyhalf(h: int) -> LF:
        return tw._stack(
            [
                tw._stack([scaled[h * 6 + v * 2 + u] for u in range(2)], axis=-2)
                for v in range(3)
            ],
            axis=-3,
        )

    c0 = lz.add(pyhalf(0), tw.fq6_mul_v(sp1))
    c1 = lz.add(pyhalf(1), sp0)
    return tw._stack([c0, c1], axis=-4)


def miller_from_coeffs(coeffs, px, py, active):
    """Batched Miller loop from prepared G2 coefficients.

    coeffs [B, N_STEPS, 2, 2, 15]; px, py [B, 15]; active [B] bool.
    Returns a normalized Fq12 limb array [B, 2, 3, 2, 15], already
    conjugated for the negative x, with inactive pairs forced to 1."""
    B = px.shape[0]
    f0 = tw.fq12_one((B,))
    neg_px = lz.sub(lz.zero_like(lf(px)), lf(px, val=P_INT - 1))
    py_l = lf(py, val=P_INT - 1)
    flags = jnp.asarray(_SQR_FLAGS)
    xs = (jnp.moveaxis(jnp.asarray(coeffs), 1, 0), flags)

    def step(f_v, x):
        row, flag = x  # row [B, 2, 2, 15]
        f = lf(f_v)
        a3 = lf(row[:, 0], val=P_INT - 1)
        a5 = tw.fq2_mul_fp(lf(row[:, 1], val=P_INT - 1), neg_px)
        sq = tw.fq12_sqr(f)
        fin = LF(
            jnp.where(flag != 0, sq.v, jnp.broadcast_to(f.v, sq.v.shape)),
            max(sq.max, f.max),
            max(sq.val, f.val),
        )
        out = _fq12_mul_line(fin, py_l, a3, a5)
        return tw._norm12(out).v, None

    f_v, _ = lax.scan(step, f0.v, xs)
    f = tw.fq12_conj(lf(f_v))  # negative BLS parameter
    one = tw.fq12_one((B,))
    sel = jnp.where(active[:, None, None, None, None], tw._norm12(f).v, one.v)
    return sel


# ------------------------------------------------------ final exponentiation


# The final-exponentiation chains are HOST-ORCHESTRATED compositions of
# small module-level jits: the powx scan — the big graph — compiles once
# per process and is REUSED six times per membership check (a single
# fused graph re-instantiated the scan per call site and took ~10 min of
# XLA time on CPU; dispatch overhead of the split is microseconds).


@jax.jit
def _powx_j(v):
    return tw._norm12(tw.fq12_powx(lf(v))).v


@jax.jit
def _mul_j(a, b):
    return tw._norm12(tw.fq12_mul(lf(a), lf(b))).v


@jax.jit
def _mul_conj_j(a, b):
    """a * conj(b), normalized."""
    return tw._norm12(tw.fq12_mul(lf(a), tw.fq12_conj(lf(b)))).v


@jax.jit
def _easy_j(v):
    """f^((p^6-1)(p^2+1)) — lands in the cyclotomic subgroup."""
    f = lf(v)
    t = tw.fq12_mul(tw.fq12_conj(f), tw.fq12_inv(f))
    return tw._norm12(tw.fq12_mul(tw.fq12_frobenius2(t), tw._norm12(t))).v


@jax.jit
def _frob1_j(v):
    return tw._norm12(tw.fq12_frobenius(lf(v))).v


@jax.jit
def _frob2_j(v):
    return tw._norm12(tw.fq12_frobenius2(lf(v))).v


@jax.jit
def _cube_j(v):
    f = lf(v)
    return tw._norm12(tw.fq12_mul(tw.fq12_sqr(f), lf(v))).v


@jax.jit
def _is_one_j(v):
    return tw.fq12_is_one(lf(v))


def final_exp_is_one(f_v):
    """True iff final_exponentiation(f) == 1, via the exact-multiple chain
    m^(3*hard) with 3H = (x-1)^2 (x+p)(x^2+p^2-1) + 3 (gcd(3, r) = 1, so
    this is 1 iff m^H is; mirrors native/bls12_381.c:1128). Takes/returns
    normalized limb arrays."""
    m = _easy_j(f_v)
    a = _mul_conj_j(_powx_j(m), m)  # m^(x-1)
    b = _mul_conj_j(_powx_j(a), a)  # m^((x-1)^2)
    c = _mul_j(_powx_j(b), _frob1_j(b))  # b^(x+p)
    d = _powx_j(_powx_j(c))  # c^(x^2)
    g = _mul_conj_j(_mul_j(d, _frob2_j(c)), c)
    return bool(_is_one_j(_mul_j(g, _cube_j(m))))


_HARD_EXP = (P_INT**4 - P_INT**2 + 1) // R_ORDER


@jax.jit
def _hard_exp_j(v):
    return tw._norm12(tw.fq12_pow_const(lf(v), _HARD_EXP)).v


def final_exponentiation(f_v):
    """Exact final exponentiation (naive hard part) — for GT export
    parity with crypto/pairing.py. Takes/returns normalized limb arrays."""
    return _hard_exp_j(_easy_j(f_v))


# ------------------------------------------------------------- public API --


# Compile units are split so each piece caches independently, and the
# Miller batch runs in FIXED-SIZE chunks: XLA compile time grows with the
# batch extent (measured: 46s at B=1, 6.4 min at B=32 on CPU), so one
# B=_CHUNK executable — compiled once per process, padded with inactive
# pairs — serves every batch size; chunk products fold through the small
# mul jit. The final-exp chain (the largest graphs) sees ONE folded
# element, so its jits also compile exactly once.
_CHUNK = 8


@jax.jit
def _miller_chunk_fold(coeffs, px, py, active):
    fs_v = miller_from_coeffs(coeffs, px, py, active)
    n = _CHUNK
    while n > 1:
        half = n // 2
        prod = tw.fq12_mul(lf(fs_v[:half]), lf(fs_v[half:n]))
        fs_v = tw._norm12(prod).v
        n = half
    return fs_v[0]


# -- mesh-sharded Miller accumulation: the chunk axis splits over the
# serve mesh; each shard scans its chunks through the SAME fixed-B=_CHUNK
# Miller body, folding a per-shard partial product, and the partials
# combine with a psum-style reduction (all_gather + Fq12 multiply — the
# reduction monoid here is multiplicative, so there is no literal psum).
# Fq12 multiplication is commutative/associative over an exact field and
# _norm12 is canonical, so the folded product — and the final membership
# verdict — is bit-identical to the sequential chunk walk.
_MILLER_SHARDED: dict[tuple, object] = {}


def _miller_sharded_fn(mesh, chunks_per_shard: int):
    key = (mesh, chunks_per_shard)
    fn = _MILLER_SHARDED.get(key)
    if fn is not None:
        return fn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from eth_consensus_specs_tpu.parallel.mesh_ops import BATCH_AXES

    def _fold_chunk(fs_v):
        n = _CHUNK
        while n > 1:
            half = n // 2
            fs_v = tw._norm12(tw.fq12_mul(lf(fs_v[:half]), lf(fs_v[half:n]))).v
            n = half
        return fs_v[0]

    def local(coeffs, px, py, active):
        def step(carry, x):
            co, px_, py_, act = x
            part = _fold_chunk(miller_from_coeffs(co, px_, py_, act))
            return tw._norm12(tw.fq12_mul(lf(carry), lf(part))).v, None

        init = tw.fq12_one(()).v
        part, _ = lax.scan(step, init, (coeffs, px, py, active))
        parts = jax.lax.all_gather(part, BATCH_AXES)  # [S, 2, 3, 2, 15]
        total = parts[0]
        for i in range(1, parts.shape[0]):
            total = tw._norm12(tw.fq12_mul(lf(total), lf(parts[i]))).v
        return total

    spec = P(BATCH_AXES)
    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=P(),
            check_vma=False,
        )
    )
    _MILLER_SHARDED[key] = fn
    return fn


def _clear_sharded_after_fork_in_child() -> None:
    # fork-safety: compiled executables reference the parent's devices
    _MILLER_SHARDED.clear()


os.register_at_fork(after_in_child=_clear_sharded_after_fork_in_child)


def _prepare_all(pairs: list) -> None:
    """Fill _PREP_CACHE for every live G2 point in `pairs` in ONE native
    lockstep walk (bls_g2_prepare_many: Montgomery batch inversions across
    all points, limbs emitted directly in the device encoding).  Fresh Qs
    are the common case on the signature path — every distinct message is
    a fresh hash-to-curve point, and the per-point host oracle walk costs
    ~5 ms each — so this is what makes the device pairing win on fresh
    batches, not just on cache-friendly fixed-setup (KZG) workloads.
    Falls back silently to per-point prepare_g2 inside _prepared()."""
    from eth_consensus_specs_tpu.crypto import native_bridge as nb

    fresh = []
    seen = set()
    for p, q in pairs:
        if p.is_infinity() or q.is_infinity():
            continue
        key = (q.x, q.y)
        if key in _PREP_CACHE or key in seen:
            continue
        seen.add(key)
        fresh.append(q)
    if len(fresh) < 2:  # a single walk gains nothing over the oracle path
        return
    rows = nb.g2_prepare_many(
        [((q.x.c0.n, q.x.c1.n), (q.y.c0.n, q.y.c1.n)) for q in fresh]
    )
    if rows is None:
        return
    if len(_PREP_CACHE) + len(fresh) > 256:
        # evict only entries THIS batch does not need — clearing wholesale
        # would push the batch's own cached points back onto the ~5 ms
        # per-point host walk the pre-fill exists to avoid
        needed = seen | {
            (q.x, q.y) for p, q in pairs if not (p.is_infinity() or q.is_infinity())
        }
        for key in [k for k in _PREP_CACHE if k not in needed]:
            del _PREP_CACHE[key]
    for q, row in zip(fresh, rows):
        _PREP_CACHE[(q.x, q.y)] = row


def _fill_chunks(pairs: list, n_chunks: int):
    """Pack pairs into [n_chunks, _CHUNK, ...] kernel inputs; unfilled
    slots stay inactive (their Miller value folds as 1)."""
    coeffs = np.zeros((n_chunks, _CHUNK, N_STEPS, 2, 2, N_LIMBS), np.uint64)
    px = np.zeros((n_chunks, _CHUNK, N_LIMBS), np.uint64)
    py = np.zeros((n_chunks, _CHUNK, N_LIMBS), np.uint64)
    active = np.zeros((n_chunks, _CHUNK), bool)
    for i, (p, q) in enumerate(pairs):
        if p.is_infinity() or q.is_infinity():
            continue
        ci, j = divmod(i, _CHUNK)
        coeffs[ci, j] = _prepared(q)
        px[ci, j], py[ci, j] = g1_affine_limbs(p)
        active[ci, j] = True
    return coeffs, px, py, active


def _miller_product(pairs: list, mesh=None):
    """Product of Miller values over (G1, G2) pairs as a normalized limb
    array, chunked to the fixed-size kernel. With a multi-device `mesh`
    and more than one chunk of pairs, the chunk axis shards over the mesh
    (per-shard partial products, psum-style Fq12 combine)."""
    from eth_consensus_specs_tpu import obs
    from eth_consensus_specs_tpu.parallel.mesh_ops import pad_to_shards, shard_count

    _prepare_all(pairs)
    n_chunks = (len(pairs) + _CHUNK - 1) // _CHUNK
    shards = shard_count(mesh)
    if shards > 1 and n_chunks > 1:
        # one chunk gains nothing from S shards; past that, pad the
        # chunk count to the mesh and let every shard walk its share
        padded = pad_to_shards(n_chunks, shards)
        coeffs, px, py, active = _fill_chunks(pairs, padded)
        obs.count("mesh.dispatches", 1)
        obs.count("mesh.sharded_items", len(pairs))
        fn = _miller_sharded_fn(mesh, padded // shards)
        return fn(
            jnp.asarray(coeffs), jnp.asarray(px), jnp.asarray(py), jnp.asarray(active)
        )
    coeffs, px, py, active = _fill_chunks(pairs, n_chunks)
    total = None
    for ci in range(n_chunks):
        part = _miller_chunk_fold(
            jnp.asarray(coeffs[ci]),
            jnp.asarray(px[ci]),
            jnp.asarray(py[ci]),
            jnp.asarray(active[ci]),
        )
        total = part if total is None else _mul_j(total, part)
    return total


def pairing_check_device(pairs: list, mesh=None) -> bool:
    """prod e(P_i, Q_i) == 1 with the Miller accumulation and final-exp
    membership check on device. Pairs are (G1 Point, G2 Point) host
    objects (subgroup-checked at deserialization)."""
    if not pairs:
        return True
    return bool(final_exp_is_one(_miller_product(pairs, mesh=mesh)))


_PREP_CACHE: dict = {}


def _prepared(q) -> np.ndarray:
    key = (q.x, q.y)
    hit = _PREP_CACHE.get(key)
    if hit is None:
        hit = prepare_g2(q)
        if len(_PREP_CACHE) > 256:
            _PREP_CACHE.clear()
        _PREP_CACHE[key] = hit
    return hit


def pairing_device(p, q) -> Fq12:
    """Exact e(P, Q) computed on device — GT element equal to
    crypto/pairing.pairing (parity/test surface; the hot path is
    pairing_check_device)."""
    if p.is_infinity() or q.is_infinity():
        return Fq12.one()
    out = final_exponentiation(_miller_product([(p, q)]))
    return tw.limbs_to_fq12(np.asarray(out))


def miller_loop_device(p, q) -> Fq12:
    """Miller value f_{|x|,Q}(P) (conjugated) — bit-exact vs
    crypto/pairing.miller_loop, for unit tests. Uses the same chunked
    kernel as the hot path (padded with inactive pairs whose f is 1, so
    the fold is exactly this pair's value)."""
    if p.is_infinity() or q.is_infinity():
        return Fq12.one()
    return tw.limbs_to_fq12(np.asarray(_miller_product([(p, q)])))
