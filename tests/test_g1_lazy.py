"""G1 point arithmetic on lazy limbs (ops/g1_msm over ops/lazy_limbs):
the formulas against crypto/curve.Point on their corner lanes, the
13 x 30 <-> 15 x 26 regrouping at a program's boundary, the batched MSM
program against the host MSM on the corners of its four-bit windows, a
lane's table, and the shape and the counters of its scalar loop."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eth_consensus_specs_tpu.crypto.curve import B1, Point, g1_generator, g1_infinity
from eth_consensus_specs_tpu.crypto.fields import Fq, P as P_INT, R
from eth_consensus_specs_tpu.crypto.msm import msm_g1
from eth_consensus_specs_tpu.ops import field_limbs as fl
from eth_consensus_specs_tpu.ops import g1_msm as gm
from eth_consensus_specs_tpu.ops import lazy_limbs as lz

G = g1_generator()
INF = g1_infinity()


def _jacobian(point: Point, z: int = 1, lift: tuple = (0, 0, 0)):
    """`point` as Jacobian Montgomery integers with Z = z, each
    coordinate raised by `lift` x p into the redundant range."""
    if point.is_infinity():
        coords = (1, 1, 0)
    else:
        coords = (point.x.n * z * z % P_INT, point.y.n * pow(z, 3, P_INT) % P_INT, z)
    return tuple(c * lz.R_INT % P_INT + k * P_INT for c, k in zip(coords, lift))


def _lanes(jacobians):
    """Rows of Jacobian integers to three u64[n, 15] arrays."""
    return tuple(
        jnp.asarray(np.stack([lz.int_to_limbs(j[c]) for j in jacobians])) for c in range(3)
    )


def _points(X, Y, Z) -> list[Point]:
    out = []
    for x, y, z in zip(*(np.asarray(a) for a in (X, Y, Z))):
        x, y, z = (lz.from_mont_int(c) for c in (x, y, z))
        if z == 0:
            out.append(INF)
            continue
        zi = pow(z, -1, P_INT)
        out.append(Point(Fq(x * zi * zi % P_INT), Fq(y * pow(zi, 3, P_INT) % P_INT), B1))
    return out


def _canonical(arrays):
    return all(
        int(np.asarray(a).max()) <= lz.MASK
        and all(lz.limbs_to_int(row) < 2 * P_INT for row in np.asarray(a))
        for a in arrays
    )


def test_dbl_matches_curve_on_corner_lanes():
    p = G.mul(5)
    y_zero = tuple(c * lz.R_INT % P_INT for c in (3, 0, 1))  # no such point: Z3 = 2YZ alone
    lanes = [
        _jacobian(p),
        _jacobian(p, z=7),
        _jacobian(p, z=7, lift=(1, 1, 1)),
        _jacobian(INF),
        (0, 0, 0),
        y_zero,
    ]
    out = jax.jit(lambda *c: gm._canon(gm._dbl(gm._wrap(*c))))(*_lanes(lanes))
    assert _canonical(out)
    assert _points(*out) == [p.mul(2)] * 3 + [INF] * 3


def test_add_matches_curve_on_corner_lanes():
    p, q = G.mul(5), G.mul(11)
    zero = (0, 0, 0)
    pairs = [
        (_jacobian(p), _jacobian(q), p + q),
        (_jacobian(p, z=3), _jacobian(q, z=9), p + q),
        (_jacobian(p, z=3, lift=(1, 1, 1)), _jacobian(q, z=9, lift=(1, 1, 1)), p + q),
        (_jacobian(p), _jacobian(p), p.mul(2)),
        (_jacobian(p, z=3), _jacobian(p, z=9, lift=(1, 0, 1)), p.mul(2)),
        (_jacobian(p), _jacobian(-p), INF),
        (_jacobian(p, z=3, lift=(0, 1, 0)), _jacobian(-p, z=9), INF),
        (zero, _jacobian(q, z=9), q),
        (_jacobian(INF), _jacobian(q, lift=(1, 1, 1)), q),
        (_jacobian(p, z=3), zero, p),
        (_jacobian(p, lift=(1, 1, 0)), _jacobian(INF, lift=(0, 0, 1)), p),
        (zero, zero, INF),
        (_jacobian(INF), _jacobian(INF, lift=(1, 1, 1)), INF),
    ]
    add = jax.jit(lambda *c: gm._canon(gm._add(gm._wrap(*c[:3]), gm._wrap(*c[3:]))))
    out = add(*_lanes([a for a, _, _ in pairs]), *_lanes([b for _, b, _ in pairs]))
    assert _canonical(out)
    assert _points(*out) == [want for _, _, want in pairs]


def test_regrouping_round_trips_and_keeps_the_value():
    rng = random.Random(32)
    values = [0, 1, P_INT - 1, P_INT, 2 * P_INT - 1]
    values += [rng.randrange(2 * P_INT) for _ in range(8)]
    # every limb pattern of either width, the all-ones rows among them
    values += [(1 << 390) - 1, (1 << 390) - (1 << 360), (1 << 30) - 1, ((1 << 26) - 1) << 364]
    packed = np.stack([fl.int_to_limbs(v) for v in values])
    lazy = np.asarray(gm._to_lazy(jnp.asarray(packed)))
    assert lazy.shape == (len(values), lz.N_LIMBS) and int(lazy.max()) <= lz.MASK
    assert [lz.limbs_to_int(row) for row in lazy] == values
    assert np.array_equal(lazy, np.stack([lz.int_to_limbs(v) for v in values]))
    back = np.asarray(gm._from_lazy(jnp.asarray(lazy)))
    assert np.array_equal(back, packed)


def test_montgomery_constants_agree_across_the_two_limb_forms():
    assert lz.R_INT == fl.R_INT
    assert np.array_equal(np.asarray(gm._to_lazy(jnp.asarray(fl.ONE_MONT))), lz.ONE_MONT)
    assert fl.from_mont_int(fl.to_mont(12345)) == lz.from_mont_int(lz.to_mont(12345)) == 12345


def _msm_case(rng, lengths):
    fixed = [0, 1, R - 1, (1 << 256) - 1]
    points, scalars = [], []
    for n in lengths:
        points.append([G.mul(rng.randrange(1, R)) for _ in range(n)])
        ks = [rng.randrange(1 << 256) for _ in range(n)]
        ks[: len(fixed)] = fixed[:n]
        rng.shuffle(ks)
        scalars.append(ks)
    return points, scalars


@pytest.mark.parametrize("lengths", [(32, 32), (8, 5, 1)], ids=["2x32", "ragged_3x8"])
def test_msm_many_kernel_equals_the_host_msm(lengths):
    rng = random.Random(len(lengths))
    points, scalars = _msm_case(rng, lengths)
    got = gm.msm_g1_many_device(points, scalars)
    assert got == [msm_g1(p, k) for p, k in zip(points, scalars)]


# scalars that walk the window's corners: the ends of a digit, a carry
# into the next window, every digit full, one live digit, and the lanes
# where a window's addition meets its special cases: r (the last window
# adds the opposite of the accumulator), r + 30 (it adds 15 P to 15 P)
WINDOW_CORNERS = [
    0, 1, 15, 16, 17, (1 << 256) - 1, 0xF << 252, 0xF,
    int("1" * 64, 16), R - 1, R, R + 30, R + 15, 2 * R, 2 * R + 30,
]


@pytest.mark.parametrize("scalar", WINDOW_CORNERS, ids=hex)
def test_msm_kernel_on_the_windows_corner_scalars(scalar):
    """One compiled shape for every case: lane 0 the corner scalar, an
    infinity point under a full scalar beside it, two live lanes."""
    points = [G.mul(7), INF, G.mul(R - 5), G.mul(12345)]
    scalars = [scalar, (1 << 256) - 1, scalar ^ 0xF0F, 3]
    assert gm.msm_g1_device(points, scalars) == msm_g1(points, scalars)


def test_all_corner_scalars_in_one_batch_with_infinity_lanes():
    """Every corner scalar as a lane of ONE execution (the 2 x 32 shape
    above): on live points, on live points with infinity points among
    them, on infinity points alone, on one point."""
    rng = random.Random(34)
    n = len(WINDOW_CORNERS)
    live = [G.mul(rng.randrange(1, R)) for _ in range(n)]
    holes = [INF if i % 4 == 2 else p for i, p in enumerate(live)]
    points = [live + holes, [INF] * n + [G] * n]
    scalars = [WINDOW_CORNERS * 2] * 2
    got = gm.msm_g1_many_device(points, scalars)
    assert got == [msm_g1(p, k) for p, k in zip(points, scalars)]
    assert gm.msm_g1_many_device([[INF] * n, live], [WINDOW_CORNERS] * 2, pad_shape=(2, 32)) == [
        INF,
        msm_g1(live, WINDOW_CORNERS),
    ]


def test_a_lanes_table_is_the_multiples_of_its_point():
    p = G.mul(5)
    lanes = [_jacobian(p), _jacobian(p, z=7, lift=(1, 1, 1)), _jacobian(INF), (0, 0, 0)]
    bits = jnp.asarray(gm._scalars_to_bits([0, 1, R + 30, 16]))
    products, tables = jax.jit(gm._scalar_loop)(bits, *_lanes(lanes))
    assert _points(*products) == [INF, p, INF, INF]
    assert all(t.shape == (gm.TABLE + 1, len(lanes), lz.N_LIMBS) for t in tables)
    assert _canonical([t.reshape(-1, lz.N_LIMBS) for t in tables])
    multiples = [p.mul(d) if d else INF for d in range(gm.TABLE)]
    for lane, want in enumerate([multiples, multiples, [INF] * gm.TABLE, [INF] * gm.TABLE]):
        assert _points(*(t[: gm.TABLE, lane] for t in tables)) == want


def test_a_table_entry_is_chosen_a_lane():
    rng = np.random.default_rng(34)
    tables = tuple(
        jnp.asarray(rng.integers(0, 1 << 26, (gm.TABLE, 3, 5, lz.N_LIMBS), dtype=np.uint64))
        for _ in range(3)
    )
    digit = rng.integers(0, gm.TABLE, (3, 5))
    digit[0, :2] = (0, gm.TABLE - 1)
    got = gm._table_entries(tables, jnp.asarray(digit, jnp.uint64))
    for g, t in zip(got, tables):
        want = np.take_along_axis(np.asarray(t), digit[None, ..., None], axis=0)[0]
        assert np.array_equal(np.asarray(g.v), want)


def test_the_formulas_multiplies_are_the_counted_ones(monkeypatch):
    calls = []
    mul = lz.mul
    monkeypatch.setattr(lz, "mul", lambda x, y: calls.append(1) or mul(x, y))
    point = gm._wrap(*(jnp.zeros((lz.N_LIMBS,), jnp.uint64),) * 3)
    gm._dbl(point)
    assert len(calls) == gm.DBL_MULS == 7
    del calls[:]
    gm._add(point, point)
    assert len(calls) == gm.ADD_MULS == 23
    assert (gm.WINDOW_BITS, gm.WINDOWS, gm.TABLE) == (4, 64, 16)
    assert (gm.SCALAR_STEPS, gm.SCALAR_FIELD_MULS) == (78, 3586)


def test_an_execution_counts_its_steps_and_its_multiplies():
    """`g1_msm.scalar_steps` and `g1_msm.field_muls` are one execution's
    sequential trips and multiplies a lane, whatever its items and lanes;
    a unit-scalar sum runs no scalar loop and counts nothing."""
    from eth_consensus_specs_tpu import obs

    def counters():
        c = obs.snapshot()["counters"]
        return c.get("g1_msm.scalar_steps", 0), c.get("g1_msm.field_muls", 0)

    points = [G.mul(7), INF, G.mul(R - 5), G.mul(12345)]
    steps0, muls0 = counters()
    gm.msm_g1_many_device([points, points[:2]], [[2, 3, 5, 7], [11, 13]], pad_shape=(2, 32))
    steps1, muls1 = counters()
    assert (steps1 - steps0, muls1 - muls0) == (78, 3586)
    gm.msm_g1_device(points, [2, 3, 5, 7])
    steps2, muls2 = counters()
    assert (steps2 - steps1, muls2 - muls1) == (78, 3586)
    gm.sum_g1_device(points)
    assert counters() == (steps2, muls2)


def _loops(jaxpr, depth=0):
    """(primitive name, depth among loops, equation) of every loop."""
    found = []
    for eqn in jaxpr.eqns:
        subs = [
            getattr(v, "jaxpr", v)
            for p in eqn.params.values()
            for v in (p if isinstance(p, (list, tuple)) else [p])
            if hasattr(getattr(v, "jaxpr", v), "eqns")
        ]
        is_loop = eqn.primitive.name in ("while", "scan")
        if is_loop:
            found.append((eqn.primitive.name, depth, eqn))
        for sub in subs:
            found += _loops(sub, depth + is_loop)
    return found


def _trips(eqn):
    """Trip count of a `scan`, or of a counted `while` (fori_loop's
    bounds lead its carry); None where the upper bound is computed."""
    if eqn.primitive.name == "scan":
        return eqn.params["length"]
    lo, hi = eqn.invars[eqn.params["cond_nconsts"] + eqn.params["body_nconsts"] :][:2]
    return int(hi.val) - int(lo.val) if hasattr(hi, "val") else None


def test_the_scalar_loop_holds_no_loop_but_its_doublings():
    """The program's loops are the algorithm's: ONE scalar loop (the
    table's steps, then the windows) with the doublings' loop inside,
    and the tree's levels. A step holds no other loop, and no loop
    anywhere comes from the field arithmetic under the doubling and the
    addition."""
    sds = jax.ShapeDtypeStruct
    args = (sds((2, 4, gm.SCALAR_BITS), jnp.uint64),) + (sds((2, 4, 13), jnp.uint64),) * 3
    loops = _loops(jax.make_jaxpr(gm.msm_many_kernel)(*args).jaxpr)
    assert sorted((name, depth, _trips(eqn)) for name, depth, eqn in loops) == [
        ("scan", 0, 2),  # the tree over 4 lanes
        ("while", 0, gm.SCALAR_STEPS),
        ("while", 1, None),  # a step's doublings: none for the table, WINDOW_BITS a window
    ]
    (steps,) = [eqn for name, depth, eqn in loops if name == "while" and depth == 0]
    (doublings,) = _loops(steps.params["body_jaxpr"].jaxpr)
    assert _loops(doublings[2].params["body_jaxpr"].jaxpr) == []
    assert gm.TABLE - 2 + gm.WINDOWS == gm.SCALAR_STEPS == 78
