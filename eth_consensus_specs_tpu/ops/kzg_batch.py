"""Device-batched KZG blob verification — the DAS workload's hot path.

An entire flush of (blob, commitment, proof) triples verifies through
exactly TWO device dispatches plus one pairing membership check:

  1. **Batched Lagrange -> monomial conversion** (``ops/fr_fft``): every
     blob polynomial of the flush rides ONE batched inverse FFT (the
     batch axis is blobs-per-flush, bucketed through the live
     ``serve/buckets.fr_fft_key``), and the challenge evaluation
     ``y_i = f_i(z_i)`` finishes as a host Horner walk over the
     coefficients. Exact modular arithmetic: the value equals the host
     oracle's barycentric ``evaluate_polynomial_in_evaluation_form``
     bit for bit, including challenges that land on a root of unity
     (no special case needed in coefficient form).
  2. **One RLC-combined G1 multi-MSM** (``ops/g1_msm.msm_many_kernel``):
     the spec's batch check needs two G1 points —
     ``A = sum r_i * proof_i`` and
     ``B = sum r_i * C_i + (-sum r_i y_i) * G + sum (z_i r_i) * proof_i``
     (the commitment-minus-y and proof-z lincombs folded into one MSM by
     linearity) — and both run as the two items of a single batched
     multi-MSM dispatch, lane-bucketed through the live
     ``serve/buckets.kzg_msm_key`` (2n+1 lanes for n blobs; the lane
     axis shards over the mesh past the crossover).
  3. **One pairing check** (``ops/pairing_device`` via the same routing
     policy the BLS batch uses): ``e(A, -tau G2) * e(B, G2) == 1`` —
     both G2 points are fixed setup points, so the prepared-coefficient
     cache makes the Miller input preparation free.

Verdict parity is a hard invariant: every verdict equals what
``crypto/kzg.py`` returns on the same inputs (the RLC singleton check is
deterministic — ``X^r == 1`` in a prime-order group with ``r != 0 mod
R`` iff ``X == 1`` — so bisection leaves equal per-blob direct calls),
and a sampled divergence watchdog recomputes one item per flush through
the pure host oracle.

Invalid items isolate through the same RLC bisection discipline
``ops/bls_batch.verify_many`` uses: one check settles an all-valid
flush; a reject bisects, recomputing only the Fiat-Shamir fold + MSM +
pairing per subset (the per-item FFT evaluations are computed ONCE).
"""

from __future__ import annotations

import os

from eth_consensus_specs_tpu import obs
from eth_consensus_specs_tpu.crypto import kzg
from eth_consensus_specs_tpu.crypto.curve import g1_generator, g2_generator
from eth_consensus_specs_tpu.crypto.fields import R as BLS_MODULUS
from eth_consensus_specs_tpu.obs import watchdog, waterfall

BYTES_PER_BLOB = kzg.BYTES_PER_BLOB
BYTES_PER_COMMITMENT = kzg.BYTES_PER_COMMITMENT
BYTES_PER_PROOF = kzg.BYTES_PER_PROOF
N_BLOB = kzg.FIELD_ELEMENTS_PER_BLOB


# ------------------------------------------------------------- parsing --


def parse_item(item: tuple[bytes, bytes, bytes]):
    """(blob, commitment, proof) -> (blob, commitment_bytes, C_point,
    polynomial, challenge, proof_bytes, proof_point) or None on ANY
    input the host oracle would reject with an assertion — the exact
    accept/reject surface of ``crypto/kzg.verify_blob_kzg_proof``, so
    per-item verdicts match :func:`verify_blob_host`."""
    blob, commitment_bytes, proof_bytes = item
    blob = bytes(blob)
    commitment_bytes = bytes(commitment_bytes)
    proof_bytes = bytes(proof_bytes)
    if (
        len(blob) != BYTES_PER_BLOB
        or len(commitment_bytes) != BYTES_PER_COMMITMENT
        or len(proof_bytes) != BYTES_PER_PROOF
    ):
        return None
    try:
        kzg.bytes_to_kzg_commitment(commitment_bytes)
        polynomial = kzg.blob_to_polynomial(blob)
        kzg.bytes_to_kzg_proof(proof_bytes)
    except AssertionError:
        return None
    challenge = kzg.compute_challenge(blob, commitment_bytes)
    return (
        blob,
        commitment_bytes,
        kzg._g1_point(commitment_bytes),
        polynomial,
        challenge,
        proof_bytes,
        kzg._g1_point(proof_bytes),
    )


def verify_blob_host(blob: bytes, commitment_bytes: bytes, proof_bytes: bytes) -> bool:
    """The per-item host oracle with the serve layer's verdict semantic:
    malformed inputs (wrong lengths, invalid G1 encodings, field
    elements >= the modulus) are ``False`` verdicts, not exceptions —
    exactly the items :func:`parse_item` rejects."""
    try:
        return bool(kzg.verify_blob_kzg_proof(bytes(blob), bytes(commitment_bytes),
                                              bytes(proof_bytes)))
    except AssertionError:
        return False


# ------------------------------------------------- challenge evaluation --


def _eval_coeffs(coeffs: list[int], z: int) -> int:
    """Horner over monomial coefficients — exact mod-R arithmetic, so it
    equals the barycentric host evaluation of the same polynomial."""
    y = 0
    for c in reversed(coeffs):
        y = (y * z + c) % BLS_MODULUS
    return y


def challenge_evaluations(parsed: list, mesh=None) -> list[int]:
    """``y_i = f_i(z_i)`` for every parsed item, with the Lagrange ->
    monomial conversion of the WHOLE flush in one batched device inverse
    FFT (``ETH_SPECS_KZG_HOST_EVAL=1`` forces the host barycentric path
    instead — bit-identical values, no device dispatch)."""
    if not parsed:
        return []
    if os.environ.get("ETH_SPECS_KZG_HOST_EVAL", "0") not in ("", "0"):
        return [
            kzg.evaluate_polynomial_in_evaluation_form(poly, z)
            for _, _, _, poly, z, _, _ in parsed
        ]
    from eth_consensus_specs_tpu.ops.fr_fft import batch_fft_field
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    # blobs carry brp(evaluation) order; natural-order rows IFFT to the
    # monomial coefficients (brp is an involution)
    with waterfall.leg("kzg.brp"):
        rows = [kzg.bit_reversal_permutation(poly) for _, _, _, poly, z, _, _ in parsed]
        roots = kzg.compute_roots_of_unity(N_BLOB)
    shards = mesh_ops.shard_count(mesh)
    use_mesh = mesh if shards > 1 and len(rows) >= mesh_ops.min_items() else None
    key = buckets.fr_fft_key(len(rows), N_BLOB, mesh=use_mesh)
    obs.count("kzg.fft_rows", len(rows))
    with buckets.first_dispatch(*key):
        coeff_rows = batch_fft_field(
            rows, roots, inv=True, mesh=use_mesh, pad_batch=key[1]
        )
    with waterfall.leg("kzg.horner"):
        return [
            _eval_coeffs(coeffs, z)
            for coeffs, (_, _, _, _, z, _, _) in zip(coeff_rows, parsed)
        ]


# ------------------------------------------------------------- RLC fold --


def _rlc_check(parsed: list, ys: list[int], mesh=None, flush_n: int | None = None) -> bool:
    """One batch verdict for a subset: the spec's Fiat-Shamir RLC
    (``crypto/kzg.verify_kzg_proof_batch`` :412) with its three G1
    lincombs folded by linearity into the two items of ONE batched
    multi-MSM dispatch, then one pairing check. ``flush_n`` is the size
    of the flush this subset was bisected out of: the subset pads into
    the flush's own lane bucket (infinity lanes), so isolating an
    invalid item never compiles a narrower shape of a kernel that takes
    minutes to compile."""
    from eth_consensus_specs_tpu.ops.bls_batch import _pairing_check_routed
    from eth_consensus_specs_tpu.ops.g1_msm import msm_g1_many_device
    from eth_consensus_specs_tpu.parallel import mesh_ops
    from eth_consensus_specs_tpu.serve import buckets

    with waterfall.leg("kzg.rlc_fold"):
        n = len(parsed)
        degree_poly = N_BLOB.to_bytes(8, kzg.KZG_ENDIANNESS)
        data = kzg.RANDOM_CHALLENGE_KZG_BATCH_DOMAIN + degree_poly + n.to_bytes(
            8, kzg.KZG_ENDIANNESS
        )
        for (_, commitment_bytes, _, _, z, proof_bytes, _), y in zip(parsed, ys):
            data += (
                commitment_bytes
                + kzg.bls_field_to_bytes(z)
                + kzg.bls_field_to_bytes(y)
                + proof_bytes
            )
        r_powers = kzg.compute_powers(kzg.hash_to_bls_field(data), n)

        proof_pts = [p for _, _, _, _, _, _, p in parsed]
        c_pts = [c for _, _, c, _, _, _, _ in parsed]
        zs = [z for _, _, _, _, z, _, _ in parsed]
        neg_ry = (-sum(rp * y for rp, y in zip(r_powers, ys))) % BLS_MODULUS
        a_lanes = (proof_pts, list(r_powers))
        b_lanes = (
            c_pts + proof_pts + [g1_generator()],
            list(r_powers)
            + [z * rp % BLS_MODULUS for z, rp in zip(zs, r_powers)]
            + [neg_ry],
        )

        shards = mesh_ops.shard_count(mesh)
        flush_n = max(flush_n or n, n)
        wide = shards > 1 and buckets.route_wide(
            "kzg", buckets.kzg_lane_bucket(flush_n, 1), flush_n
        )
        use_mesh = mesh if wide else None
        key = buckets.kzg_msm_key(flush_n, mesh=use_mesh)
        obs.count("kzg.batches", 1)
    with buckets.first_dispatch(*key):
        a_pt, b_pt = msm_g1_many_device(
            [a_lanes[0], b_lanes[0]], [a_lanes[1], b_lanes[1]],
            mesh=use_mesh, pad_shape=(2, key[1]),
        )
    with waterfall.leg("kzg.pairing"):
        setup = kzg.get_setup()
        return _pairing_check_routed(
            [(a_pt, -setup.g2_monomial[1]), (b_pt, g2_generator())], mesh=use_mesh
        )


def verify_blob_kzg_proof_batch_device(
    blobs, commitments_bytes, proofs_bytes, mesh=None
) -> bool:
    """Device twin of ``crypto/kzg.verify_blob_kzg_proof_batch``: same
    assertion surface for malformed inputs, bit-identical verdict for
    well-formed ones."""
    assert len(blobs) == len(commitments_bytes) == len(proofs_bytes)
    if not blobs:
        return True
    parsed = [
        parse_item(item) for item in zip(blobs, commitments_bytes, proofs_bytes)
    ]
    assert all(p is not None for p in parsed), "malformed blob/commitment/proof"
    with obs.span("kzg.verify_many", items=len(parsed)):
        obs.count("kzg.blobs_verified", len(parsed))
        ys = challenge_evaluations(parsed, mesh=mesh)
        return _rlc_check(parsed, ys, mesh=mesh)


# ------------------------------------------------------------ bisection --


def _bisect(parsed: list, ys: list[int], mesh=None, flush_n: int | None = None) -> list[bool]:
    flush_n = flush_n or len(parsed)
    if _rlc_check(parsed, ys, mesh=mesh, flush_n=flush_n):
        return [True] * len(parsed)
    if len(parsed) == 1:
        obs.count("kzg.isolated_invalid", 1)
        return [False]
    mid = len(parsed) // 2
    return _bisect(parsed[:mid], ys[:mid], mesh=mesh, flush_n=flush_n) + _bisect(
        parsed[mid:], ys[mid:], mesh=mesh, flush_n=flush_n
    )


def verify_many_blobs(
    items: list[tuple[bytes, bytes, bytes]], mesh=None, parsed: list | None = None
) -> list[bool]:
    """Per-item verdicts for many (blob, commitment, proof) triples —
    the serving layer's batch entry point. Parsing and the per-item
    challenge evaluations are computed ONCE; one RLC check settles an
    all-valid flush, and a reject bisects with only the Fiat-Shamir fold
    + MSM + pairing per subset. Malformed items are ``False`` without
    poisoning the rest (the :func:`verify_blob_host` semantic).

    ``parsed`` lets the serve batch thread hand over work it already did
    off the dispatch thread (one entry per item, ``None`` for malformed
    ones — exactly :func:`parse_item`'s output)."""
    if not items:
        return []
    if parsed is None:
        parsed = [parse_item(it) for it in items]
    assert len(parsed) == len(items)
    out = [False] * len(items)
    live = [i for i, p in enumerate(parsed) if p is not None]
    if not live:
        return out
    with obs.span("kzg.verify_many", items=len(live)):
        obs.count("kzg.blobs_verified", len(live))
        sub = [parsed[i] for i in live]
        ys = challenge_evaluations(sub, mesh=mesh)
        for i, v in zip(live, _bisect(sub, ys, mesh=mesh)):
            out[i] = v
    # sampled device/host coupling (outside the span, like bls_batch):
    # one item's verdict must reproduce through the pure host oracle —
    # barycentric evaluation, Pippenger MSM, host pairing, no fr_fft
    if watchdog.should_check("kzg_batch"):
        k = live[watchdog.call_salt("kzg_batch") % len(live)]
        blob, commitment_bytes, _, _, _, proof_bytes, _ = parsed[k]
        host = verify_blob_host(blob, commitment_bytes, proof_bytes)
        watchdog.record(
            "kzg_batch", host == out[k],
            {"device": out[k], "host": host, "item": k},
        )
    return out
