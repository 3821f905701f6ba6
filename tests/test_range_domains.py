"""Boundary-value tests GENERATED from the registry's declared domains.

The ``Variant.domains`` declarations are load-bearing twice: they seed
rangelint's interval proof AND (here) they generate runtime corner
tests. Every corner value executed below is read out of the registry —
never hard-coded — so a stale or weakened declaration fails at runtime
against the family's host oracle, not just on paper.

Fast lane: declaration self-consistency for every variant, the cheap
hash-word families (sha256, merkle, merkle_many, shuffle) and the
host-side canonical-domain check for the pairing's prepared inputs.
Slow lane (nightly, like the rest of the device-crypto suite): the
minutes-scale compiles — state_root's post-epoch tree, and the
limb-arithmetic families executed at their Montgomery corners (fr_fft,
g1_msm, bls_msm, the pairing's active-mask corners)."""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eth_consensus_specs_tpu.analysis import kernels


def _variant(name):
    spec = kernels.by_name()[name]
    variants = spec.build_variants(None)
    assert variants, name
    return variants[0]


def _corners(dom):
    assert dom.corners, f"domain {dom.name!r} declares no corners"
    return list(dom.corners)


def _obj(a):
    return np.asarray(a).astype(object)


# ----------------------------------------------------- declaration hygiene


def test_every_variant_declares_domains_and_corners_are_members():
    """One Domain per traced input leaf, bounds inside the dtype lane,
    every declared corner a member of its own domain — the minimum for
    the corner tests below (and the prover's seeds) to mean anything."""
    checked = 0
    for spec in kernels.REGISTRY:
        for v in spec.build_variants(None):
            traced = [
                a
                for i, a in enumerate(v.args)
                if i not in (v.static_argnums or ())
            ]
            leaves = jax.tree_util.tree_leaves(traced)
            assert len(v.domains) == len(leaves), (spec.name, v.label)
            for dom, leaf in zip(v.domains, leaves):
                dt = np.dtype(leaf.dtype)
                lane_max = 1 if dt == np.bool_ else int(np.iinfo(dt).max)
                lo, hi = _obj(dom.lo), _obj(dom.hi)
                assert np.all(lo >= 0), (spec.name, dom.name)
                assert np.all(hi <= lane_max), (spec.name, dom.name)
                assert np.all(lo <= hi), (spec.name, dom.name)
                for lab, c in dom.corners:
                    c = _obj(c)
                    assert np.all(lo <= c) and np.all(c <= hi), (
                        spec.name,
                        dom.name,
                        lab,
                    )
                checked += 1
    assert checked >= 25, "registry lost domain coverage"


def test_montgomery_domains_declare_the_issue_corners():
    """The ISSUE's named boundary members, read back from the registry:
    all-zero limbs and p-1 everywhere, 2p-1 on the redundant domains —
    and NOT on the pairing's canonical (< p) domains, whose absence IS
    the declared _fat_p precondition."""
    msm = _variant("g1_msm")
    for dom in msm.domains[1:]:
        labels = {lab for lab, _ in _corners(dom)}
        assert {"zero", "p-1", "2p-1"} <= labels, dom.name
    for dom in _variant("pairing").domains[:3]:
        labels = {lab for lab, _ in _corners(dom)}
        assert "p-1" in labels and "2p-1" not in labels, dom.name


# ------------------------------------------------------- hash-word families


def test_sha256_word_corners_vs_hashlib():
    from eth_consensus_specs_tpu.ops.sha256 import sha256_64B_batch_np

    dom = _variant("sha256").domains[0]
    for label, w in _corners(dom):
        msg = np.full((16,), w, dtype=np.uint32).astype(">u4").view(np.uint8)
        out = sha256_64B_batch_np(msg.reshape(1, 64))
        assert out[0].tobytes() == hashlib.sha256(msg.tobytes()).digest(), label


def _host_tree_root(chunks: list[bytes]) -> bytes:
    while len(chunks) > 1:
        chunks = [
            hashlib.sha256(chunks[i] + chunks[i + 1]).digest()
            for i in range(0, len(chunks), 2)
        ]
    return chunks[0]


def test_merkle_leaf_corners_vs_hashlib():
    from eth_consensus_specs_tpu.ops.merkle import _tree_root_fused

    dom = _variant("merkle").domains[0]
    depth = 4
    for label, w in _corners(dom):
        leaves = np.full((1 << depth, 8), w, dtype=np.uint32)
        root = np.asarray(_tree_root_fused(jnp.asarray(leaves), depth))
        want = _host_tree_root([r.astype(">u4").tobytes() for r in leaves])
        assert root.astype(">u4").tobytes() == want, label


def test_merkle_many_batch_corners_vs_hashlib():
    from eth_consensus_specs_tpu.ops.merkle import _many_tree_root_fused

    dom = _variant("merkle_many").domains[0]
    depth, batch = 3, 4
    for label, w in _corners(dom):
        leaves = np.full((batch, 1 << depth, 8), w, dtype=np.uint32)
        roots = np.asarray(_many_tree_root_fused(jnp.asarray(leaves), depth))
        want = _host_tree_root([r.astype(">u4").tobytes() for r in leaves[0]])
        for b in range(batch):
            assert roots[b].astype(">u4").tobytes() == want, label


def test_merkle_inc_corners_vs_hashlib():
    """Forest-update corners from the registry's declared domains: the
    leaf/node lanes at their hash-word corners and the dirty mask at
    both of its corners (all-clean = identity, all-dirty = dense
    rebuild), against the host tree oracle."""
    from eth_consensus_specs_tpu.ops import merkle_inc as mi

    spec = kernels.by_name()["merkle_inc"]
    v = spec.build_variants(None)[0]
    words_dom, mask_dom = v.domains[0], v.domains[1]
    depth = 3
    n = 1 << depth
    for wlab, w in _corners(words_dom):
        leaves = np.full((n, 8), w, dtype=np.uint32)
        nodes = mi.build_forest(jnp.asarray(leaves), 1)
        want = _host_tree_root([r.astype(">u4").tobytes() for r in leaves])
        for mlab, m in _corners(mask_dom):
            mask = np.full((1, n), bool(m))
            out, root = mi._apply_kernel(depth, 2, 2)(
                nodes, jnp.asarray(mask), jnp.asarray(leaves[None])
            )
            assert np.asarray(root).astype(">u4").tobytes() == want, (wlab, mlab)
            nodes = out


def test_shuffle_corners_stay_bijective():
    """Swap-or-not at every (seed-word, pivot, count) corner: whatever the
    digest bits say, the live part of the list must remain a rearrangement
    of itself — the property the consensus shuffle's invertibility rests
    on — and the lanes past the count must stay where they were."""
    from eth_consensus_specs_tpu.ops.shuffle import shuffle_rounds_kernel

    v = _variant("shuffle")
    words_dom, pivot_dom, count_dom, _ = v.domains
    lanes = v.args[3].shape[0]
    rounds = v.args[1].shape[0]
    active = np.arange(lanes, dtype=np.int32)[::-1].copy()
    for wlab, w in _corners(words_dom):
        for plab, pv in _corners(pivot_dom):
            for clab, n in _corners(count_dom):
                seed_words = np.full((8,), w, np.uint32)
                pivots = np.full((rounds,), min(pv, n - 1), np.int32)
                out = np.asarray(shuffle_rounds_kernel(seed_words, pivots, np.int32(n), active))
                assert sorted(out[:n].tolist()) == sorted(active[:n].tolist()), (wlab, plab, clab)
                assert (out[n:] == active[n:]).all(), (wlab, plab, clab)


@pytest.mark.slow  # two full post-epoch tree compiles, ~90 s on CPU
def test_state_root_u64_corners_vs_host_oracle():
    from eth_consensus_specs_tpu.forks import get_spec
    from eth_consensus_specs_tpu.ops import state_root_host as srh
    from eth_consensus_specs_tpu.ops.state_columns import JustificationState
    from eth_consensus_specs_tpu.ops.state_root import (
        StateRootArrays,
        post_epoch_state_root,
        synthetic_static,
    )

    v = _variant("state_root")
    # the three u64 columns (balances/effective_balance/inactivity) share
    # one declared full-lane domain; exercise BOTH its corners
    bal_dom = v.domains[6]
    assert "u64" in bal_dom.name

    spec = get_spec("altair", "minimal")
    n = 32
    arrays, meta = synthetic_static(spec, n)
    arrays_np = StateRootArrays(*[np.asarray(a) for a in arrays])
    zero32 = np.zeros(32, np.uint8)
    just = JustificationState(
        current_epoch=jnp.uint64(5),
        justification_bits=jnp.asarray([True, False, True, False]),
        prev_justified_epoch=jnp.uint64(3),
        prev_justified_root=jnp.asarray(zero32),
        cur_justified_epoch=jnp.uint64(4),
        cur_justified_root=jnp.asarray(zero32),
        finalized_epoch=jnp.uint64(2),
        finalized_root=jnp.asarray(zero32),
        block_root_prev=jnp.asarray(zero32),
        block_root_cur=jnp.asarray(zero32),
        slashings_sum=jnp.uint64(0),
    )
    for label, cv in _corners(bal_dom):
        col_np = np.full((n,), np.uint64(cv), np.uint64)
        col = jnp.asarray(col_np)
        dev = np.asarray(post_epoch_state_root(arrays, meta, col, col, col, just))
        host = srh.post_epoch_state_root_np(
            arrays_np, meta, col_np, col_np, col_np, just
        )
        assert np.array_equal(dev, host), label


def test_pairing_prepared_inputs_live_in_the_declared_canonical_domain():
    """The pairing declares its prepared inputs canonical (< p) — the
    precondition _fat_p's lend cover is sized from. Check the REAL
    host-side preparation against the declared caps, limb by limb, so
    the declaration can never drift from what runtime actually feeds."""
    from eth_consensus_specs_tpu.crypto.curve import g1_generator, g2_generator
    from eth_consensus_specs_tpu.ops import pairing_device as dev

    coeff_dom, px_dom, py_dom, _mask = _variant("pairing").domains
    p1, q1 = g1_generator().mul(7), g2_generator().mul(11)
    row = dev.prepare_g2(q1)
    assert np.all(row.astype(object) <= _obj(coeff_dom.hi)), coeff_dom.name
    px, py = dev.g1_affine_limbs(p1)
    assert np.all(px.astype(object) <= _obj(px_dom.hi)), px_dom.name
    assert np.all(py.astype(object) <= _obj(py_dom.hi)), py_dom.name


# -------------------------------------------------- limb-arithmetic families
# device double-and-add / FFT executions — nightly lane like their suites


@pytest.mark.slow
def test_fr_fft_montgomery_corners_vs_host_fft():
    from eth_consensus_specs_tpu.crypto import das
    from eth_consensus_specs_tpu.crypto.kzg import compute_roots_of_unity
    from eth_consensus_specs_tpu.ops.fr_fft import FR, batch_fft_mont

    v = _variant("fr_fft")
    vals_dom = v.domains[0]
    n = v.args[0].shape[1]
    roots = compute_roots_of_unity(n)
    for label, cv in _corners(vals_dom):
        row = (
            np.zeros(FR.n_limbs, np.uint64)
            if np.ndim(cv) == 0
            else np.asarray(cv, np.uint64)
        )
        if np.ndim(cv) == 0:
            assert int(cv) == 0, "scalar Montgomery corners must be zero"
        vals = np.broadcast_to(row, (1, n, FR.n_limbs))
        out = np.asarray(batch_fft_mont(jnp.asarray(vals), roots))
        a = FR.from_mont_int(row)
        want = das.fft_field([a] * n, roots)
        got = [FR.from_mont_int(out[0, i]) for i in range(n)]
        assert got == want, label


def _limbs_value(limbs, limb_bits=30):
    return sum(int(x) << (limb_bits * i) for i, x in enumerate(limbs))


@pytest.mark.slow
def test_g1_msm_scalar_corners_and_redundant_coordinates_vs_host():
    """Scalar-bit corners (all-zero -> infinity, all-one -> the max
    scalar) and the redundant [p, 2p) coordinate encodings the domain's
    2p-1 corner admits: the kernel must produce the same group element
    the host oracle computes from the canonical values."""
    from eth_consensus_specs_tpu.crypto.curve import g1_generator, g1_infinity
    from eth_consensus_specs_tpu.crypto.fields import P as P_INT
    from eth_consensus_specs_tpu.crypto.msm import msm_g1
    from eth_consensus_specs_tpu.ops import g1_msm as gm
    from eth_consensus_specs_tpu.ops.field_limbs import int_to_limbs

    v = _variant("g1_msm")
    bits_dom, coord_dom = v.domains[0], v.domains[1]
    lanes = v.args[1].shape[0]
    G = g1_generator()
    pts = [G.mul(k + 1) for k in range(lanes)]
    X, Y, Z = gm._points_to_limbs(pts)

    for label, bit in _corners(bits_dom):
        bits = np.full((lanes, gm.SCALAR_BITS), bit, np.uint64)
        out = gm.msm_kernel(
            jnp.asarray(bits), jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z)
        )
        got = gm._jacobian_to_point(*(np.asarray(o) for o in out))
        k = 0 if int(bit) == 0 else (1 << gm.SCALAR_BITS) - 1
        assert got == msm_g1(pts, [k] * lanes), label

    # redundant encodings: value + p, still limb-wise inside the domain
    def red(arr):
        out = np.stack([int_to_limbs(_limbs_value(r) + P_INT) for r in arr])
        assert np.all(out.astype(object) <= _obj(coord_dom.hi)), (
            "redundant encoding escaped the declared [0, 2p) domain"
        )
        return out

    ones = np.ones((lanes, gm.SCALAR_BITS), np.uint64)
    out = gm.msm_kernel(
        jnp.asarray(ones), jnp.asarray(red(X)), jnp.asarray(red(Y)), jnp.asarray(red(Z))
    )
    got = gm._jacobian_to_point(*(np.asarray(o) for o in out))
    kmax = (1 << gm.SCALAR_BITS) - 1
    assert got == msm_g1(pts, [kmax] * lanes)

    # the all-zero coordinate corner: Z = 0 lanes ARE the infinity encoding
    zero = np.zeros_like(X)
    out = gm.msm_kernel(jnp.asarray(ones), jnp.asarray(zero), jnp.asarray(zero), jnp.asarray(zero))
    assert gm._jacobian_to_point(*(np.asarray(o) for o in out)) == g1_infinity()


@pytest.mark.slow
def test_bls_msm_per_item_sums_at_corners_vs_host():
    from eth_consensus_specs_tpu.crypto.curve import g1_generator, g1_infinity
    from eth_consensus_specs_tpu.crypto.msm import msm_g1
    from eth_consensus_specs_tpu.ops import g1_msm as gm

    v = _variant("bls_msm")
    items, lanes = v.args[0].shape[:2]
    assert items >= 2
    G = g1_generator()
    pts = [G.mul(j + 1) for j in range(lanes)]
    X = np.zeros((items, lanes, 13), np.uint64)
    Y = np.zeros_like(X)
    Z = np.zeros_like(X)
    X[0], Y[0], Z[0] = gm._points_to_limbs(pts)
    # item 1..: all-zero lanes — the declared zero corner, i.e. infinity
    outX, outY, outZ = (
        np.asarray(o)
        for o in gm.sum_many_kernel(jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z))
    )
    assert gm._jacobian_to_point(outX[0], outY[0], outZ[0]) == msm_g1(
        pts, [1] * lanes
    )
    for i in range(1, items):
        assert gm._jacobian_to_point(outX[i], outY[i], outZ[i]) == g1_infinity()


def test_g2_aggregate_domains_declare_redundant_corners():
    """The G2 aggregation family declares the REDUNDANT [0, 2p) range
    (its scan carry crosses the boundary < 2p), so all three Jacobian
    coordinate domains must carry the zero / p-1 / 2p-1 corners."""
    for dom in _variant("g2_aggregate").domains:
        labels = {lab for lab, _ in _corners(dom)}
        assert {"zero", "p-1", "2p-1"} <= labels, dom.name


@pytest.mark.slow
def test_g2_aggregate_corners_vs_host():
    """Kernel execution at the declared corners: the all-zero corner is
    the infinity encoding (Z = 0 -> every sum infinity), and redundant
    [p, 2p) coordinate encodings — admitted by the 2p-1 corner — must
    produce the same group elements as the canonical host fold."""
    from eth_consensus_specs_tpu.crypto.curve import g2_generator, g2_infinity
    from eth_consensus_specs_tpu.crypto.fields import P as P_INT
    from eth_consensus_specs_tpu.crypto.signature import _sum_g2
    from eth_consensus_specs_tpu.ops import g2_aggregate as ga
    from eth_consensus_specs_tpu.ops import lazy_limbs as lz

    coord_dom = _variant("g2_aggregate").domains[0]
    items, lanes = 2, 4

    # zero corner: all-zero limbs everywhere == every lane at infinity
    zero = np.zeros((items, lanes, 2, lz.N_LIMBS), np.uint64)
    rX, rY, rZ = (
        np.asarray(o)
        for o in ga.g2_sum_many_kernel(*(jnp.asarray(zero),) * 3)
    )
    for i in range(items):
        assert ga._jacobian_to_point(rX[i], rY[i], rZ[i]) == g2_infinity()

    # redundant encodings: every Fq limb row re-encoded as value + p,
    # still limb-wise inside the declared [0, 2p) domain
    pts = [[g2_generator().mul(k + 1) for k in range(lanes)] for _ in range(items)]
    X, Y, Z = ga._points_to_lanes(pts, items, lanes)

    def red(arr):
        out = np.empty_like(arr)
        for idx in np.ndindex(arr.shape[:-1]):
            row = arr[idx]
            if not row.any():
                out[idx] = row  # infinity lanes stay the zero encoding
                continue
            val = lz.limbs_to_int(row) + P_INT
            out[idx] = lz.int_to_limbs(val)
            assert np.all(out[idx].astype(object) <= _obj(coord_dom.hi)), (
                "redundant encoding escaped the declared [0, 2p) domain"
            )
        return out

    rX, rY, rZ = (
        np.asarray(o)
        for o in ga.g2_sum_many_kernel(
            jnp.asarray(red(X)), jnp.asarray(red(Y)), jnp.asarray(red(Z))
        )
    )
    for i in range(items):
        assert ga._jacobian_to_point(rX[i], rY[i], rZ[i]) == _sum_g2(pts[i])


@pytest.mark.slow
def test_pairing_active_mask_corners_vs_host_miller():
    """Both corners of the declared active-mask domain in one chunk:
    active lanes fold their host Miller values, inactive lanes (the
    all-zero-limb rows _fill_chunks leaves behind) fold as one — and an
    all-inactive chunk is EXACTLY Fq12.one()."""
    from eth_consensus_specs_tpu.crypto import pairing as host_pairing
    from eth_consensus_specs_tpu.crypto.curve import g1_generator, g2_generator
    from eth_consensus_specs_tpu.ops import fq12_tower as tw
    from eth_consensus_specs_tpu.ops import pairing_device as dev

    mask_dom = _variant("pairing").domains[3]
    assert {int(c) for _, c in _corners(mask_dom)} == {0, 1}

    pairs = [
        (g1_generator().mul(7), g2_generator().mul(11)),
        (g1_generator().mul(5), g2_generator().mul(3)),
    ]
    dev._prepare_all(pairs)
    coeffs, px, py, active = dev._fill_chunks(pairs, 1)
    assert active[0].tolist() == [True, True] + [False] * (dev._CHUNK - 2)
    f = dev._miller_chunk_fold(
        jnp.asarray(coeffs[0]),
        jnp.asarray(px[0]),
        jnp.asarray(py[0]),
        jnp.asarray(active[0]),
    )
    want = host_pairing.miller_loop(
        pairs[0][0], host_pairing.untwist(pairs[0][1])
    ) * host_pairing.miller_loop(pairs[1][0], host_pairing.untwist(pairs[1][1]))
    assert tw.limbs_to_fq12(np.asarray(f)) == want

    coeffs, px, py, active = dev._fill_chunks([], 1)
    f = dev._miller_chunk_fold(
        jnp.asarray(coeffs[0]),
        jnp.asarray(px[0]),
        jnp.asarray(py[0]),
        jnp.asarray(active[0]),
    )
    one = type(want).one()
    assert tw.limbs_to_fq12(np.asarray(f)) == one


def test_kzg_msm_domains_declare_the_corners():
    """The 12th family (the KZG RLC fold's batched multi-MSM) declares
    the same contract as g1_msm: scalar bits in {0, 1} and redundant
    [0, 2p) Jacobian coordinates with the zero / p-1 / 2p-1 corners —
    the zero coordinate corner IS the infinity-lane encoding the blob
    batch pads with."""
    v = _variant("kzg_msm")
    assert {int(c) for _, c in _corners(v.domains[0])} == {0, 1}
    for dom in v.domains[1:]:
        labels = {lab for lab, _ in _corners(dom)}
        assert {"zero", "p-1", "2p-1"} <= labels, dom.name


@pytest.mark.slow
def test_kzg_msm_per_item_msms_at_corners_vs_host():
    """msm_many_kernel at the declared corners, against the host
    Pippenger oracle: all-zero scalar bits -> every item infinity,
    all-one bits -> the max 256-bit scalar per lane, and an item of
    all-zero coordinate lanes (the declared zero corner = the infinity
    padding the blob flush uses) -> infinity regardless of bits."""
    from eth_consensus_specs_tpu.crypto.curve import g1_generator, g1_infinity
    from eth_consensus_specs_tpu.crypto.msm import msm_g1
    from eth_consensus_specs_tpu.ops import g1_msm as gm

    v = _variant("kzg_msm")
    items, lanes = v.args[0].shape[:2]
    assert items >= 2
    G = g1_generator()
    pts = [G.mul(j + 1) for j in range(lanes)]
    pX, pY, pZ = gm._points_to_limbs(pts)
    X = np.zeros((items, lanes, 13), np.uint64)
    Y = np.zeros_like(X)
    Z = np.zeros_like(X)
    # item 0 carries real points; item 1.. stays the all-zero coordinate
    # corner (infinity lanes)
    X[0], Y[0], Z[0] = pX, pY, pZ
    bits_dom = v.domains[0]
    for label, bit in _corners(bits_dom):
        bits = np.full((items, lanes, gm.SCALAR_BITS), bit, np.uint64)
        oX, oY, oZ = (
            np.asarray(o)
            for o in gm.msm_many_kernel(
                jnp.asarray(bits), jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Z)
            )
        )
        k = 0 if int(bit) == 0 else (1 << gm.SCALAR_BITS) - 1
        assert gm._jacobian_to_point(oX[0], oY[0], oZ[0]) == msm_g1(
            pts, [k] * lanes
        ), label
        for i in range(1, items):
            assert gm._jacobian_to_point(oX[i], oY[i], oZ[i]) == g1_infinity(), label


@pytest.mark.slow
def test_kzg_challenge_evaluation_at_fr_root_of_unity_edges_vs_host_oracle():
    """The kzg_batch evaluation path at the Fr roots-of-unity EDGE
    values (w^0 = 1, w^1, w^(n-1) — the boundary members of the
    evaluation domain) and at the field's own edges (0, r-1) as
    challenges: the device inverse-FFT + Horner value must equal the
    crypto/kzg.py barycentric oracle bit for bit, in-domain special
    case included."""
    from eth_consensus_specs_tpu.crypto import kzg
    from eth_consensus_specs_tpu.ops import kzg_batch

    n = kzg.FIELD_ELEMENTS_PER_BLOB
    roots = kzg.compute_roots_of_unity(n)
    poly = [(j * 7919 + 3) % kzg.BLS_MODULUS for j in range(n)]
    blob = b"".join(kzg.bls_field_to_bytes(x) for x in poly)
    base = kzg_batch.parse_item((blob, kzg.G1_POINT_AT_INFINITY,
                                 kzg.G1_POINT_AT_INFINITY))
    assert base is not None
    edges = [roots[0], roots[1], roots[n - 1], 0, kzg.BLS_MODULUS - 1]
    parsed = []
    for z in edges:
        row = list(base)
        row[4] = z
        parsed.append(tuple(row))
    got = kzg_batch.challenge_evaluations(parsed)
    want = [kzg.evaluate_polynomial_in_evaluation_form(poly, z) for z in edges]
    assert got == want
