"""G1 point arithmetic on lazy limbs (ops/g1_msm over ops/lazy_limbs):
the formulas against crypto/curve.Point on their corner lanes, the
13 x 30 <-> 15 x 26 regrouping at a program's boundary, the batched MSM
program against the host MSM, and the shape of its bit loop."""

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


def _loops(jaxpr, depth=0):
    """(primitive name, depth among loops, body jaxprs) of every loop."""
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


def test_the_bit_loop_has_no_loop_inside():
    """A scalar bit is straight-line code: the field arithmetic under
    the doubling and the addition brings no loop of its own."""
    sds = jax.ShapeDtypeStruct
    args = (sds((2, 4, gm.SCALAR_BITS), jnp.uint64),) + (sds((2, 4, 13), jnp.uint64),) * 3
    loops = _loops(jax.make_jaxpr(gm.msm_many_kernel)(*args).jaxpr)
    bit_loops = [eqn for name, depth, eqn in loops if name == "while" and depth == 0]
    assert len(bit_loops) == 1
    assert _loops(bit_loops[0].params["body_jaxpr"].jaxpr) == []
    assert all(depth == 0 for _, depth, _ in loops)
