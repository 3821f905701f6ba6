"""Device-batched PeerDAS cell-proof verification — a block's data column
sidecars in one flush.

A Fulu node checks cells, not blobs: every ``DataColumnSidecar`` carries
one cell and one proof a blob, all cells of one column index, and is
valid iff ``verify_data_column_sidecar`` (structure) and
``verify_data_column_sidecar_kzg_proofs`` (one
``verify_cell_kzg_proof_batch`` over its cells) hold
(specs/fulu/p2p-interface.md). A flush of sidecars verifies through the
universal verification equation (specs/fulu/polynomial-commitments-
sampling.md:403-507; ``crypto/das.verify_cell_kzg_proof_batch_impl``)
over ALL its cells at once, with the work laid out so that a reject
costs no second device execution:

  1. **fold** (leg ``das.fold``): the flush's commitments deduplicated,
     the spec's Fiat-Shamir challenge over every cell in request order,
     its powers ``r^k``, and the cells' evaluations as ONE array of the
     bytes they arrived in, ``uint8[cells, 64, 32]``: a cell's 2,048
     bytes are its 64 canonical elements in bit-reversed order, which is
     the order the transform's butterflies take, so no integer is made
     of an element.
  2. **interpolation**: only ``h^-t * sum_k r^k c_k[t]`` a sidecar is
     ever read of the cells' interpolation polynomials ``c_k`` (the coset
     unshift ``h^-t`` is shared by a sidecar's cells: one column index),
     and the inverse FFT is linear, so ONE device program
     (``ops/fr_fft.fold_program``) cuts the limbs from the bytes, weights
     every row by its ``r^k``, adds a sidecar's rows, transforms the 64
     points of a row a SIDECAR and scales it by ``h^-t / 64``. The leg
     ``das.interp_fold`` is that program's host side (the ``r^k`` as
     limbs, a segment id a row, a column index a sidecar); 64 integers a
     sidecar come back. On the host route (below) the cells become
     integers, each row goes through the host's FFT and the same leg
     folds the rows as host integers (:func:`_interp_fold`).
  3. **the multi-MSM**: ONE ``ops/g1_msm.msm_many_kernel`` execution whose
     items are the sidecars, two each: ``A_j = sum_k r^k pi_k`` and
     ``B_j = sum_k r^k h_j^64 pi_k`` over the sidecar's proofs.
  4. **the check** (leg ``das.check``): for a run of sidecars the partial
     sums added, ``RLC`` over the distinct commitments, ``RLI`` over 64
     setup points, ONE pairing of two pairs:
     ``e(sum A_j, [s^64]_2) == e(RLC - RLI + sum B_j, [1]_2)``.
     One check settles an all-valid flush. A reject bisects over SIDECARS
     from the partial sums and the per-sidecar folds it already has, with
     the flush's own ``r^k``: the hash bound them to every input before
     any subset was chosen, so a subset's check is the same random linear
     combination restricted to its cells, and no device program runs
     again.

Routing is by what the code observes: the two programs run on the device
for buckets that ``serve.buckets.precompile`` has compiled (minutes for
the limb kernel; its ``das_msm`` key warms the multi-MSM and, under
``serve.buckets.das_fold_key``, the folding interpolation of a block
that fills the bucket), and through the host's FFT and the C core's MSM
otherwise, so no flush compiles on the thread that serves it. Verdicts
are the same either way: every value between the legs is an exact
integer or group element.

A malformed sidecar (index out of range, lengths unequal or zero, a
field element not below the modulus, bytes that are no G1 point of the
subgroup) is ``False`` and takes no part in its flush. The blob limit of
the sidecar's epoch and the inclusion proof need its block header and
are the caller's.
"""

from __future__ import annotations

import os
import time
from functools import lru_cache
from typing import NamedTuple

import numpy as np

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto import das, kzg
from eth_consensus_specs_tpu.crypto import native_bridge as nb
from eth_consensus_specs_tpu.crypto.curve import B1, Point, g1_infinity
from eth_consensus_specs_tpu.crypto.fields import Fq
from eth_consensus_specs_tpu.crypto.fields import R as BLS_MODULUS
from eth_consensus_specs_tpu.obs import watchdog, waterfall

NUMBER_OF_COLUMNS = das.CELLS_PER_EXT_BLOB
N_CELL = das.FIELD_ELEMENTS_PER_CELL
BYTES_PER_CELL = das.BYTES_PER_CELL

_MODULUS_BE = np.frombuffer(BLS_MODULUS.to_bytes(32, "big"), np.uint8)


class Column(NamedTuple):
    """One well-formed sidecar, parsed: what :func:`prepare_columns`
    hands a flush."""

    index: int
    cells: tuple  # 2,048 bytes each
    commitments: tuple  # 48 bytes each
    commitment_points: tuple  # crypto.curve.Point each, decoded once a flush
    proofs: tuple  # 48 bytes each
    proof_points: tuple


# ------------------------------------------------------------- parsing --


def _canonical(cells: bytes) -> bool:
    """Every 32-byte big-endian field element of `cells` below the modulus
    (``bytes_to_bls_field``'s assertion), as one array comparison."""
    rows = np.frombuffer(cells, np.uint8).reshape(-1, 32)
    differs = rows != _MODULUS_BE
    first = differs.argmax(axis=1)
    at = np.arange(len(rows))
    return bool((differs[at, first] & (rows[at, first] < _MODULUS_BE[first])).all())


def _shape(item) -> tuple | None:
    """(index, column, commitments, proofs) of a sidecar whose structure
    holds (``verify_data_column_sidecar``, the byte lengths and field
    elements ``verify_cell_kzg_proof_batch`` asserts), else None."""
    try:
        index, column, commitments, proofs = item
        index = int(index)
        column = tuple(bytes(c) for c in column)
        commitments = tuple(bytes(c) for c in commitments)
        proofs = tuple(bytes(p) for p in proofs)
    except (TypeError, ValueError):
        return None
    if not 0 <= index < NUMBER_OF_COLUMNS or not commitments:
        return None
    if len(column) != len(commitments) or len(column) != len(proofs):
        return None
    if (
        any(len(c) != BYTES_PER_CELL for c in column)
        or any(len(c) != kzg.BYTES_PER_COMMITMENT for c in commitments)
        or any(len(p) != kzg.BYTES_PER_PROOF for p in proofs)
    ):
        return None
    return (index, column, commitments, proofs) if _canonical(b"".join(column)) else None


def _decode_g1(encodings: list[bytes]) -> list:
    """Each 48-byte encoding as a Point, or None where
    ``validate_kzg_g1`` would refuse it: one call of the C core for them
    all where it is there."""
    if not encodings:
        return []
    if nb.enabled():
        affine, status = nb.g1_decompress_many(b"".join(encodings))
        view = memoryview(affine)
        return [
            None if s == 0 else g1_infinity() if s == 2 else Point(
                Fq(int.from_bytes(view[96 * i : 96 * i + 48], "big")),
                Fq(int.from_bytes(view[96 * i + 48 : 96 * i + 96], "big")),
                B1,
            )
            for i, s in enumerate(status)
        ]
    out = []
    for b in encodings:
        try:
            kzg.validate_kzg_g1(b)
        except AssertionError:
            out.append(None)
        else:
            out.append(kzg._g1_point(b))
    return out


def prepare_columns(items: list) -> list:
    """One :class:`Column` a sidecar, None for a malformed one. The
    flush's distinct commitments are decoded once (a block's 128 sidecars
    carry the same 21) and all its proofs in one batch."""
    shaped = [_shape(item) for item in items]
    distinct = list(dict.fromkeys(c for s in shaped if s for c in s[2]))
    commitment_point = dict(zip(distinct, _decode_g1(distinct)))
    proof_points = iter(_decode_g1([p for s in shaped if s for p in s[3]]))
    out = []
    for s in shaped:
        if s is None:
            out.append(None)
            continue
        index, cells, commitments, proofs = s
        c_pts = tuple(commitment_point[c] for c in commitments)
        p_pts = tuple(next(proof_points) for _ in proofs)
        ok = all(p is not None for p in c_pts + p_pts)
        out.append(Column(index, cells, commitments, c_pts, proofs, p_pts) if ok else None)
    return out


def verify_column_host(item) -> bool:
    """The spec's verdict on one sidecar ALONE, through the host oracle
    (``crypto/das.verify_cell_kzg_proof_batch``); a malformed sidecar is
    ``False``, not an exception: exactly those :func:`prepare_columns`
    refuses."""
    shaped = _shape(item)
    if shaped is None:
        return False
    index, column, commitments, proofs = shaped
    try:
        return bool(das.verify_cell_kzg_proof_batch(
            list(commitments), [index] * len(column), list(column), list(proofs)
        ))
    except AssertionError:
        return False


# ------------------------------------------------------------ the fold --


@lru_cache(maxsize=1)
def _coset_tables() -> tuple:
    """For each column index: (h^64, the 64 powers of h^-1), h the coset
    shift of that column's cells. Constants of the domain."""
    out = []
    for index in range(NUMBER_OF_COLUMNS):
        h = das.coset_shift_for_cell(index)
        out.append((
            pow(h, N_CELL, BLS_MODULUS),
            tuple(kzg.compute_powers(pow(h, -1, BLS_MODULUS), N_CELL)),
        ))
    return tuple(out)


_BRP_CELL = np.array(kzg.bit_reversal_permutation(list(range(N_CELL))))


class _Fold(NamedTuple):
    commitments: list  # the flush's distinct commitments, as Points
    starts: list  # cell k of sidecar j is starts[j] + its row
    r_powers: list
    weights: list  # per sidecar: {distinct commitment: sum of its cells' r^k}
    cells: np.ndarray  # uint8[cells, 64, 32]: a cell's elements as its own bytes


def _fold(columns: list) -> _Fold:
    """Dedup, the spec's challenge
    (``compute_verify_cell_kzg_proof_batch_challenge`` over every cell of
    the flush in request order), its powers, the commitment weights a
    sidecar, and the cells as one array. A cell's evaluations are
    canonical, so ``bls_field_to_bytes`` of each is the cell's own bytes."""
    place: dict[bytes, int] = {}
    points = []
    for col in columns:
        for c, pt in zip(col.commitments, col.commitment_points):
            if c not in place:
                place[c] = len(place)
                points.append(pt)
    starts, total = [], 0
    for col in columns:
        starts.append(total)
        total += len(col.proofs)
    endian = kzg.KZG_ENDIANNESS
    parts = [
        das.RANDOM_CHALLENGE_KZG_CELL_BATCH_DOMAIN,
        kzg.FIELD_ELEMENTS_PER_BLOB.to_bytes(8, endian),
        N_CELL.to_bytes(8, endian),
        len(place).to_bytes(8, endian),
        total.to_bytes(8, endian),
        *place,
    ]
    for col in columns:
        index = col.index.to_bytes(8, endian)
        for c, cell, proof in zip(col.commitments, col.cells, col.proofs):
            parts += (place[c].to_bytes(8, endian), index, cell, proof)
    r_powers = kzg.compute_powers(kzg.hash_to_bls_field(b"".join(parts)), total)
    weights = []
    for col, start in zip(columns, starts):
        w: dict[int, int] = {}
        for row, c in enumerate(col.commitments):
            w[place[c]] = w.get(place[c], 0) + r_powers[start + row]
        weights.append(w)
    cells = np.frombuffer(b"".join(cell for col in columns for cell in col.cells), np.uint8)
    return _Fold(points, starts, r_powers, weights, cells.reshape(total, N_CELL, 32))


def _host_coefficients(fold: _Fold) -> list:
    """The host route's inverse FFT, a transform a cell. A cell holds its
    coset's evaluations in bit-reversed order: natural order is what the
    host's transform interpolates from, and integers are what it takes."""
    view = memoryview(fold.cells[:, _BRP_CELL].tobytes())
    flat = [int.from_bytes(view[i : i + 32], "big") for i in range(0, len(view), 32)]
    obs.count("das.boundary_ints", len(flat))
    roots = kzg.compute_roots_of_unity(N_CELL)
    return [
        das.fft_field(flat[i : i + N_CELL], roots, inv=True) for i in range(0, len(flat), N_CELL)
    ]


def _interp_fold(columns: list, fold: _Fold, coeff_rows: list) -> list:
    """Per sidecar, the 64 coefficients of ``sum_k r^k I_k``: the inverse
    FFT's rows weighted and added, then the coset unshift ``h^-t`` once a
    sidecar (its cells share the column index). The host route's; the
    device route's program does the same (:func:`_device_interp`)."""
    tables = _coset_tables()
    out = []
    for col, start in zip(columns, fold.starts):
        n = len(col.proofs)
        rs = fold.r_powers[start : start + n]
        unshift = tables[col.index][1]
        out.append([
            sum(map(int.__mul__, column, rs)) % BLS_MODULUS * u % BLS_MODULUS
            for column, u in zip(zip(*coeff_rows[start : start + n]), unshift)
        ])
    return out


# -------------------------------------------------- the two programs --


def _bucket_keys(columns: list) -> tuple[tuple, tuple]:
    from eth_consensus_specs_tpu.serve import buckets

    cells = sum(len(col.proofs) for col in columns)
    widest = max(len(col.proofs) for col in columns)
    return (
        buckets.fr_fft_key(cells, N_CELL),
        buckets.das_msm_key(2 * len(columns), widest),
    )


def _raw(points) -> list:
    """Points as the C core takes them: (x, y) integers, None at infinity."""
    return [None if p.is_infinity() else (p.x.n, p.y.n) for p in points]


def _point(raw) -> Point:
    return g1_infinity() if raw is None else Point(Fq(raw[0]), Fq(raw[1]), B1)


def _host_msm(points, scalars) -> Point:
    if nb.enabled():
        return _point(nb.g1_msm(_raw(points), scalars))
    from eth_consensus_specs_tpu.crypto.msm import msm_g1

    return msm_g1(list(points), list(scalars))


def _sum_points(points: list) -> Point:
    if nb.enabled():
        return _point(nb.g1_aggregate(_raw(points)))
    total = g1_infinity()
    for p in points:
        total = total + p
    return total


@lru_cache(maxsize=1)
def _device_unshift():
    """u64[128, 64, L] on the device, resident like the twiddles: for each
    column index the plain limbs of ``h^-t / 64``, the coset unshift and
    the inverse transform's scale in the one multiply that leaves
    Montgomery form."""
    import jax.numpy as jnp

    from eth_consensus_specs_tpu.obs import ledger
    from eth_consensus_specs_tpu.ops.fr_fft import FR

    n_inv = pow(N_CELL, -1, BLS_MODULUS)
    flat = [u * n_inv % BLS_MODULUS for _, unshift in _coset_tables() for u in unshift]
    table = jnp.asarray(FR.ints_to_limbs_batch(flat).reshape(NUMBER_OF_COLUMNS, N_CELL, -1))
    ledger.register("trusted_setup", "das_coset_unshift", int(table.nbytes))
    return table


# fork-safety, as ops/fr_fft's twiddles: the table references the parent's device
os.register_at_fork(after_in_child=_device_unshift.cache_clear)


def _device_interp(columns: list, fold: _Fold, key: tuple) -> list:
    """Per sidecar, the 64 coefficients of ``h^-t * sum_k r^k I_k`` from
    ONE device execution over the flush's bytes: what
    :func:`_interp_fold` makes of the host's transforms."""
    from eth_consensus_specs_tpu.ops import fr_fft
    from eth_consensus_specs_tpu.serve import buckets

    _, rows, segments = key
    total = len(fold.cells)
    with waterfall.leg("das.interp_fold"):
        weights = np.zeros((rows, fr_fft.FR.n_limbs), np.uint64)
        weights[:total] = fr_fft.FR.ints_to_limbs_batch(fold.r_powers)
        # padded rows weigh 0 whatever their segment; padded segments stay 0
        segment_of = np.full(rows, len(columns) - 1, np.int32)
        segment_of[:total] = np.repeat(
            np.arange(len(columns), dtype=np.int32), [len(col.proofs) for col in columns]
        )
        scale_rows = np.zeros(segments, np.int32)
        scale_rows[: len(columns)] = [col.index for col in columns]
    obs.count("das.fold_rows_device", total)
    obs.count("das.boundary_ints", len(columns) * N_CELL)
    with buckets.first_dispatch(*key):
        return fr_fft.batch_ifft_folded(
            fold.cells, kzg.compute_roots_of_unity(N_CELL), weights, segment_of,
            _device_unshift(), scale_rows, live=len(columns),
        )


def warm_fold(rows: int, segments: int) -> None:
    """Compile (or load) the folding interpolation at (rows, segments):
    one zero cell through :func:`_device_interp`'s program, the answer
    discarded. ``serve.buckets.precompile``'s."""
    from eth_consensus_specs_tpu.ops import fr_fft

    fr_fft.batch_ifft_folded(
        np.zeros((1, N_CELL, 32), np.uint8), kzg.compute_roots_of_unity(N_CELL),
        np.zeros((rows, fr_fft.FR.n_limbs), np.uint64), np.zeros(rows, np.int32),
        _device_unshift(), np.zeros(segments, np.int32), live=1,
    )


def _partial_sums(columns: list, fold: _Fold, msm_key: tuple, device: bool) -> tuple[list, list]:
    """(A_j, B_j) a sidecar: ``sum_k r^k pi_k`` and ``sum_k r^k h_j^64
    pi_k`` over its proofs, all in ONE device execution (two items a
    sidecar), or an MSM of the C core each."""
    tables = _coset_tables()
    point_lists, scalar_lists = [], []
    for shifted in (False, True):
        for col, start in zip(columns, fold.starts):
            rs = fold.r_powers[start : start + len(col.proofs)]
            if shifted:
                h64 = tables[col.index][0]
                rs = [r * h64 % BLS_MODULUS for r in rs]
            point_lists.append(list(col.proof_points))
            scalar_lists.append(rs)
    t0 = time.perf_counter()
    if device:
        from eth_consensus_specs_tpu.ops.g1_msm import msm_g1_many_device
        from eth_consensus_specs_tpu.serve import buckets

        with buckets.first_dispatch(*msm_key):
            sums = msm_g1_many_device(point_lists, scalar_lists, pad_shape=msm_key[1:3])
    else:
        sums = [_host_msm(p, s) for p, s in zip(point_lists, scalar_lists)]
    obs.observe("das.msm_call_ms", (time.perf_counter() - t0) * 1e3)
    return sums[: len(columns)], sums[len(columns) :]


# ------------------------------------------------------------ the check --


class _Flush(NamedTuple):
    """What a check of any run of sidecars reads: nothing here is
    computed again while a reject is isolated."""

    commitments: list
    weights: list
    interp: list
    a_sums: list
    b_sums: list


def _check(flush: _Flush, lo: int, hi: int) -> bool:
    """The verification equation over sidecars lo..hi-1 of the flush."""
    from eth_consensus_specs_tpu.ops.bls_batch import _pairing_check_routed

    t0 = time.perf_counter()
    with waterfall.leg("das.check"):
        weights = [0] * len(flush.commitments)
        for w in flush.weights[lo:hi]:
            for i, r in w.items():
                weights[i] += r
        interp = [sum(column) % BLS_MODULUS for column in zip(*flush.interp[lo:hi])]
        setup = kzg.get_setup()
        ll = _sum_points(flush.a_sums[lo:hi])
        rlc = _host_msm(flush.commitments, [w % BLS_MODULUS for w in weights])
        rli = _host_msm(setup.g1_monomial[:N_CELL], interp)
        rl = _sum_points([rlc, -rli, *flush.b_sums[lo:hi]])
        ok = _pairing_check_routed(
            [(ll, setup.g2_monomial[N_CELL]), (rl, -setup.g2_monomial[0])]
        )
    obs.observe("das.rlc_check_ms", (time.perf_counter() - t0) * 1e3)
    return ok


def _bisect(flush: _Flush, lo: int, hi: int) -> list[bool]:
    if _check(flush, lo, hi):
        return [True] * (hi - lo)
    if hi - lo == 1:
        obs.count("das.isolated_invalid", 1)
        return [False]
    mid = lo + (hi - lo) // 2
    return _bisect(flush, lo, mid) + _bisect(flush, mid, hi)


def verify_many_columns(items: list, parsed: list | None = None) -> list[bool]:
    """Per-sidecar verdicts for many ``(index, column, kzg_commitments,
    kzg_proofs)``: the serving layer's batch entry point. ONE inverse FFT
    and ONE multi-MSM a flush whatever it holds (the module doc); a
    malformed sidecar is ``False`` without poisoning the rest.

    ``parsed`` lets the serve batch thread hand over what it decoded off
    the dispatch thread (:func:`prepare_columns`' output)."""
    if not items:
        return []
    if parsed is None:
        parsed = prepare_columns(items)
    assert len(parsed) == len(items)
    out = [False] * len(items)
    live = [i for i, p in enumerate(parsed) if p is not None]
    if not live:
        return out
    columns = [parsed[i] for i in live]
    from eth_consensus_specs_tpu.serve import buckets

    with obs.span("das.verify_many", items=len(columns)):
        obs.count("das.columns_verified", len(columns))
        fft_key, msm_key = _bucket_keys(columns)
        interp_key = buckets.das_fold_key(fft_key[1], len(columns))
        device = buckets.is_compiled(*interp_key) and buckets.is_compiled(*msm_key)
        with waterfall.leg("das.fold"):
            fold = _fold(columns)
        obs.count("das.fft_rows", len(fold.cells))
        if device:
            interp = _device_interp(columns, fold, interp_key)
        else:
            coeff_rows = _host_coefficients(fold)
            with waterfall.leg("das.interp_fold"):
                interp = _interp_fold(columns, fold, coeff_rows)
        a_sums, b_sums = _partial_sums(columns, fold, msm_key, device)
        flush = _Flush(fold.commitments, fold.weights, interp, a_sums, b_sums)
        for i, v in zip(live, _bisect(flush, 0, len(columns))):
            out[i] = v
    # sampled coupling, outside the span as in kzg_batch: one sidecar's
    # verdict through the pure host oracle, alone
    if watchdog.should_check("das_batch"):
        k = live[watchdog.call_salt("das_batch") % len(live)]
        host = verify_column_host(items[k])
        watchdog.record("das_batch", host == out[k], {"device": out[k], "host": host, "item": k})
    return out
