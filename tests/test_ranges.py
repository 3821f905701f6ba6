"""ranges — the interval interpreter: transfer functions, loops, rules.

Unit tests for the pure transfer functions (exact python-int interval
arithmetic — the foundation everything else trusts), then the
DELIBERATE-FINDING acceptance tests: a synthetic kernel built to
overflow MUST fire lane-overflow, a sha256-style wrap with its ``Wrap``
declaration removed MUST fire, a scan whose declared invariant is not
inductive MUST fire, and a mask over an unproven magnitude MUST fire
mask-consistency. A prover whose alarms never ring proves nothing."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eth_consensus_specs_tpu.analysis.ranges import (
    Ival,
    RangeInterp,
    Wrap,
    ival_binop,
    ival_join,
    ival_leq,
)


def _run(fn, in_ivals, *args, wraps=(), widen_steps=None):
    closed = jax.make_jaxpr(fn)(*args)
    interp = RangeInterp(wraps=wraps, widen_steps=widen_steps)
    outs = interp.run(closed, in_ivals)
    return outs, interp


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------ transfer functions


def test_binop_add_sub_mul_exact():
    a, b = Ival(2, 5), Ival(10, 20)
    assert (ival_binop("add", a, b).lo, ival_binop("add", a, b).hi) == (12, 25)
    assert (ival_binop("sub", a, b).lo, ival_binop("sub", a, b).hi) == (-18, -5)
    assert (ival_binop("mul", a, b).lo, ival_binop("mul", a, b).hi) == (20, 100)
    # mixed-sign mul takes the corner extrema
    m = ival_binop("mul", Ival(-3, 2), Ival(-5, 7))
    assert (m.lo, m.hi) == (-21, 15)


def test_binop_arbitrary_precision_never_wraps():
    # the whole point: bounds are python ints, not numpy lanes
    big = (1 << 64) - 1
    iv = ival_binop("mul", Ival(0, big), Ival(0, big))
    assert iv.hi == big * big  # > 2^127, exact


def test_binop_shifts():
    a = Ival(8, 1024)
    assert (ival_binop("shift_right_logical", a, Ival(2, 3)).lo,
            ival_binop("shift_right_logical", a, Ival(2, 3)).hi) == (1, 256)
    s = ival_binop("shift_left", a, Ival(1, 4), dtype=jnp.uint64)
    assert (s.lo, s.hi) == (16, 16384)


def test_binop_shifts_negative_operands_stay_sound():
    # shift_left: a negative lo moves AWAY from zero as the shift grows,
    # so [-4, 1] << [0, 3] must cover -32 (not stop at -4)
    s = ival_binop("shift_left", Ival(-4, 1), Ival(0, 3), dtype=jnp.int64)
    assert (s.lo, s.hi) == (-32, 8)
    # ...and a fully-negative hi uses the SMALL shift for its max
    s = ival_binop("shift_left", Ival(-4, -2), Ival(1, 3), dtype=jnp.int64)
    assert (s.lo, s.hi) == (-32, -4)
    # shift_right_arithmetic: negatives move TOWARD zero as the shift
    # grows — [-100, -8] >> [0, 2] reaches -100 (lo@smin) and -2 (hi@smax)
    s = ival_binop("shift_right_arithmetic", Ival(-100, -8), Ival(0, 2))
    assert (s.lo, s.hi) == (-100, -2)
    # shift_right_logical reinterprets the bit pattern: a possibly-
    # negative int32 input covers the huge-positive result, not [0, 0]
    s = ival_binop("shift_right_logical", Ival(-1, 5), Ival(4, 8),
                   dtype=jnp.int32)
    assert s.lo == 0 and s.hi == ((1 << 32) - 1) >> 4
    # nonneg inputs keep the exact bounds
    s = ival_binop("shift_right_logical", Ival(16, 64), Ival(2, 4))
    assert (s.lo, s.hi) == (1, 16)


def test_binop_and_or_xor_masks():
    a = Ival(0, 0xABC)
    mask = Ival(0xFF, 0xFF)
    assert ival_binop("and", a, mask).hi == 0xFF  # min of the his
    o = ival_binop("or", a, mask, dtype=jnp.uint32)
    assert o.hi == 0xABC + 0xFF  # x|y <= x+y for nonneg
    assert o.lo == 0xFF  # or can only set bits
    assert ival_binop("xor", a, mask, dtype=jnp.uint32).lo == 0


def test_binop_elementwise_bounds():
    hi = np.array([3, 5, 7], dtype=object)
    iv = ival_binop("add", Ival(0, hi), Ival(1, 1))
    assert list(iv.hi) == [4, 6, 8]


def test_interval_join_and_leq():
    a, b = Ival(2, 5), Ival(4, 9)
    j = ival_join(a, b)
    assert (j.lo, j.hi) == (2, 9)
    assert ival_leq(a, j) and ival_leq(b, j)
    assert not ival_leq(j, a)
    # taint is ordered: tainted ⊄ untainted
    assert not ival_leq(Ival(0, 1, tainted=True), Ival(0, 1))
    assert ival_leq(Ival(0, 1), Ival(0, 1, tainted=True))


def test_select_and_concat_transfer():
    def sel(c, a, b):
        return jnp.where(c, a, b)

    outs, interp = _run(
        sel,
        [Ival(0, 1), Ival(5, 10), Ival(100, 200)],
        _sds((4,), jnp.bool_), _sds((4,), jnp.uint32), _sds((4,), jnp.uint32),
    )
    assert interp.events == []
    assert (int(np.min(outs[0].lo)), int(np.max(outs[0].hi))) == (5, 200)

    def cat(a, b):
        return jnp.concatenate([a, b])

    outs, interp = _run(
        cat,
        [Ival(0, 7), Ival(0, 1000)],
        _sds((2,), jnp.uint32), _sds((3,), jnp.uint32),
    )
    # positional structure preserved: first rows keep the tight bound
    hi = np.asarray(outs[0].hi)
    assert [int(x) for x in hi] == [7, 7, 1000, 1000, 1000]


# ------------------------------------------------- deliberate lane-overflow


def test_column_sum_proof_30_bits_clean_31_bits_fires():
    """THE proof from the limb_field comment, both directions: a column
    of 13 products of 30-bit limbs plus carries stays under 2^64 — and
    at 31-bit limbs it does NOT, which must fire lane-overflow."""

    def column(a, b):
        acc = jnp.zeros(a.shape[:-1], jnp.uint64)
        for i in range(13):
            acc = acc + a[..., i] * b[..., 12 - i]
        return acc

    args = (_sds((4, 13), jnp.uint64), _sds((4, 13), jnp.uint64))

    lim30 = Ival(0, (1 << 30) - 1)
    outs, interp = _run(column, [lim30, lim30], *args)
    assert interp.events == [], [e.message for e in interp.events]
    assert int(np.max(np.asarray(outs[0].hi))) == 13 * ((1 << 30) - 1) ** 2

    lim31 = Ival(0, (1 << 31) - 1)
    _, interp = _run(column, [lim31, lim31], *args)
    kinds = {e.kind for e in interp.events}
    assert "overflow" in kinds, "13-term column at 31-bit limbs MUST overflow"


def test_unsanctioned_wrap_fires_and_wrap_declaration_silences():
    """A sha256-style mod-2^32 add: without the Wrap declaration it is a
    lane-overflow finding; with the per-site declaration it is clean."""

    def wrapping_add(a, b):
        return a + b  # mod 2^32 by design — but is the design DECLARED?

    args = (_sds((8,), jnp.uint32), _sds((8,), jnp.uint32))
    full = Ival(0, 0xFFFFFFFF)

    _, interp = _run(wrapping_add, [full, full], *args)
    assert any(e.kind == "overflow" and e.prim == "add" for e in interp.events)

    _, interp = _run(
        wrapping_add, [full, full], *args,
        wraps=(Wrap("add", "test_ranges.py::wrapping_add"),),
    )
    assert interp.events == []
    assert interp.stats["wrap_hits"] == 1


def test_wrap_site_matching_is_per_site_not_blanket():
    """The Wrap declaration names ONE function — a different overflow in
    the same file still fires."""

    def other_add(a, b):
        return a + b

    args = (_sds((8,), jnp.uint32), _sds((8,), jnp.uint32))
    full = Ival(0, 0xFFFFFFFF)
    _, interp = _run(
        other_add, [full, full], *args,
        wraps=(Wrap("add", "test_ranges.py::wrapping_add"),),
    )
    assert any(e.kind == "overflow" for e in interp.events)


def test_underflow_on_unsigned_fires():
    def sub(a, b):
        return a - b

    args = (_sds((4,), jnp.uint64), _sds((4,), jnp.uint64))
    _, interp = _run(sub, [Ival(0, 10), Ival(0, 20)], *args)
    assert any("underflows" in e.message for e in interp.events)


# --------------------------------------------------------------- scan loops


def test_converging_carry_recurrence_is_inductive():
    """The carry-sweep recurrence carry' = (col + carry) >> 30 stabilizes
    in a few joins — no widening, no findings, and the final carry bound
    is the fixed point."""

    def sweep(cols):
        def step(carry, col):
            cur = col + carry
            return cur >> jnp.uint64(30), cur & jnp.uint64((1 << 30) - 1)

        carry, out = jax.lax.scan(step, jnp.zeros((4,), jnp.uint64), cols)
        return carry, out

    col_hi = 13 * ((1 << 30) - 1) ** 2  # the column bound proved above
    outs, interp = _run(
        sweep, [Ival(0, col_hi)], _sds((25, 4), jnp.uint64)
    )
    assert interp.events == [], [e.message for e in interp.events]
    assert interp.stats["widened_loops"] == 0
    # fixed point: carry <= (col_hi + carry) >> 30 (+ the second-order
    # carry-of-carry term, itself < 64)
    assert int(np.max(np.asarray(outs[0].hi))) <= (col_hi >> 30) + 64


def test_non_inductive_scan_invariant_fires_widened():
    """A genuinely growing carry (doubling per step, data-dependent so
    unrolling can't rescue it) has NO inductive interval: the carry must
    widen to dtype-top and fire the unproven-loop finding."""

    def grower(xs):
        def step(carry, x):
            nxt = carry + carry + x  # doubles every step: no fixed point
            return nxt, nxt

        return jax.lax.scan(step, jnp.ones((2,), jnp.uint64), xs)

    _, interp = _run(
        grower, [Ival(0, 1 << 32)], _sds((64, 2), jnp.uint64), widen_steps=6
    )
    assert interp.stats["widened_loops"] == 1
    assert any(e.kind == "widened" for e in interp.events), (
        "a non-inductive carry MUST be reported as unproven"
    )


def test_concrete_xs_scan_unrolls_to_exact_proof():
    """A scan indexed by arange xs (the Montgomery red_step shape) whose
    carry genuinely grows per-step unrolls with static indices instead of
    widening — the per-position proof survives."""

    def shifter(t):
        def step(t, i):
            upd = jax.lax.dynamic_slice_in_dim(t, i, 1, axis=-1)[..., 0] + 1
            return jax.lax.dynamic_update_slice_in_dim(
                t, upd[..., None], i, axis=-1
            ), None

        out, _ = jax.lax.scan(step, t, jnp.arange(8, dtype=jnp.int32))
        return out

    outs, interp = _run(shifter, [Ival(0, 100)], _sds((2, 8), jnp.uint64))
    assert interp.events == []
    assert interp.stats["unrolled_scans"] == 1
    # exact result: every position bumped exactly once, nothing widened
    assert int(np.max(np.asarray(outs[0].hi))) == 101
    assert int(np.min(np.asarray(outs[0].lo))) == 1


# --------------------------------------------------------- mask-consistency


def test_masking_unproven_magnitude_fires_masked_taint():
    """AND-ing dtype-top taint (here: from a widened loop) with a low-bit
    mask pretends to extract a limb of a magnitude nothing proved."""

    def launder(xs):
        def step(carry, x):
            nxt = carry + carry + x
            return nxt, nxt

        grown, _ = jax.lax.scan(step, jnp.ones((2,), jnp.uint64), xs)
        return grown & jnp.uint64((1 << 26) - 1)

    _, interp = _run(
        launder, [Ival(0, 1 << 32)], _sds((64, 2), jnp.uint64), widen_steps=4
    )
    assert any(e.kind == "masked-taint" for e in interp.events), (
        "masking an unproven value MUST fire mask-consistency"
    )


def test_masking_taint_with_array_shaped_mask_still_fires():
    """A broadcast constant mask reaches the AND eqn with an exact
    elementwise interval — uniform array masks must not be a blind spot
    the taint can hide under."""

    def launder(xs):
        def step(carry, x):
            nxt = carry + carry + x
            return nxt, nxt

        grown, _ = jax.lax.scan(step, jnp.ones((2,), jnp.uint64), xs)
        return grown & jnp.full((2,), (1 << 26) - 1, jnp.uint64)

    _, interp = _run(
        launder, [Ival(0, 1 << 32)], _sds((64, 2), jnp.uint64), widen_steps=4
    )
    assert any(e.kind == "masked-taint" for e in interp.events), (
        "an array-shaped uniform mask over taint MUST still fire"
    )


def test_while_cond_arithmetic_is_checked():
    """The cond jaxpr runs on device once per iteration — an overflowing
    multiply inside it must fire even when the body is clean."""

    def loop(x):
        def cond(c):
            return c * jnp.uint64(1 << 40) < jnp.uint64(1 << 63)

        def body(c):
            return c

        return jax.lax.while_loop(cond, body, x)

    _, interp = _run(loop, [Ival(0, 1 << 32)], _sds((), jnp.uint64))
    assert any(e.kind == "overflow" for e in interp.events), (
        "u64 overflow inside a while COND must fire lane-overflow"
    )


def test_reduce_or_and_are_bitwise_not_minmax():
    """1|2 = 3 exceeds the elementwise max and 1&2 = 0 undershoots the
    elementwise min — the reduce transfer must cover the bit union."""

    def red_or(x):
        return jnp.bitwise_or.reduce(x, axis=0)

    def red_and(x):
        return jnp.bitwise_and.reduce(x, axis=0)

    outs, _ = _run(red_or, [Ival(0, 2)], _sds((4,), jnp.uint32))
    assert int(np.max(np.asarray(outs[0].hi))) >= 3  # bit-union cover
    outs, _ = _run(red_and, [Ival(1, 2)], _sds((4,), jnp.int32))
    assert int(np.min(np.asarray(outs[0].lo))) == 0  # AND can clear bits
    # bools keep the exact and==min transfer (jnp.all -> reduce_and)
    outs, _ = _run(lambda x: jnp.all(x, axis=0), [Ival(1, 1)],
                   _sds((4,), jnp.bool_))
    assert int(np.min(np.asarray(outs[0].lo))) == 1


def test_scan_widening_one_carry_rechecks_the_others():
    """Widening c1 to top can un-stabilize a dependent carry (c0 =
    c1 >> 32 is [0, 0] while c1 stays small): the kept carries must be
    re-checked against the WIDENED environment, or the analyzer
    certifies a tight interval runtime values escape."""

    def loop(xs):
        def step(carry, x):
            c0, c1 = carry
            # c1 >> 40 stays exactly 0 while c1 is small (pre-widening
            # c0 looks perfectly inductive) but reaches ~2^24 once c1
            # is topped — only the re-check can catch it
            return (c1 >> jnp.uint64(40), c1 + x), c0

        return jax.lax.scan(
            step, (jnp.zeros((2,), jnp.uint64), jnp.ones((2,), jnp.uint64)), xs
        )

    outs, interp = _run(
        loop, [Ival(0, 1 << 32)], _sds((64, 2), jnp.uint64), widen_steps=4
    )
    c0 = outs[0]
    assert c0.tainted or int(np.max(np.asarray(c0.hi))) >= (1 << 20), (
        f"non-inductive dependent carry kept a stale tight interval: {c0}"
    )


def test_length_zero_scan_output_covers_init():
    """A length-0 scan never runs its body: the carry output IS init, so
    the stable path must join init in (a body like ``c & 0xFF`` would
    otherwise certify [0, 255] for an un-reduced 2^30 init)."""

    def loop(c):
        out, _ = jax.lax.scan(
            lambda c, _: (c & jnp.uint64(0xFF), None), c, None, length=0
        )
        return out

    outs, _ = _run(loop, [Ival(0, 1 << 30)], _sds((2,), jnp.uint64))
    assert int(np.max(np.asarray(outs[0].hi))) >= (1 << 30), (
        f"length-0 scan output must cover init: {outs[0]}"
    )


def test_add_any_is_an_add_not_a_crash():
    """Transpose-of-fan-out accumulation (grad) emits ``add_any`` — it
    must go through the add transfer, not KeyError the whole run."""
    fn = jax.grad(lambda x: jnp.sum(x) + jnp.sum(x * 2.0))
    outs, interp = _run(fn, [Ival(0, 0)], _sds((4,), jnp.float32))
    assert not any(e.kind == "unhandled" for e in interp.events)


def test_div_rem_possibly_negative_divisors_stay_sound():
    # x // -1 = -x: a negative divisor flips the quotient's sign
    d = ival_binop("div", Ival(0, 10), Ival(-5, 5))
    assert d.lo <= -10 and d.hi >= 10
    # |rem| reaches |divisor| - 1 for the LARGEST-magnitude divisor
    r = ival_binop("rem", Ival(0, 200), Ival(-100, 5))
    assert r.lo <= -99 and r.hi >= 99
    # ...but never exceeds |dividend|
    r = ival_binop("rem", Ival(0, 3), Ival(-100, 5))
    assert (r.lo, r.hi) == (-3, 3)
    # the nonneg fast path stays exact
    d = ival_binop("div", Ival(10, 100), Ival(2, 5))
    assert (d.lo, d.hi) == (2, 50)
    r = ival_binop("rem", Ival(0, 200), Ival(1, 7))
    assert (r.lo, r.hi) == (0, 6)


def test_masking_proven_carry_separation_is_clean():
    """The legitimate pattern: (x & mask) with (x >> bits) separately
    carried — the interval proves the mask only truncates carry bits."""

    def split(a, b):
        s = a + b  # provably < 2^27, in-lane
        return s & jnp.uint64((1 << 26) - 1), s >> jnp.uint64(26)

    norm = Ival(0, (1 << 26) - 1)
    outs, interp = _run(
        split, [norm, norm], _sds((4,), jnp.uint64), _sds((4,), jnp.uint64)
    )
    assert interp.events == []
    assert int(np.max(np.asarray(outs[0].hi))) == (1 << 26) - 1
    assert int(np.max(np.asarray(outs[1].hi))) == 1  # the carry bit, exact


# ------------------------------------------------------------ trusted bound


def test_wrap_bound_declares_trusted_invariant():
    """Wrap(bound=B) clamps a sanctioned site's result to [0, B] — the
    borrow-restore idiom: transient underflow, restored under the mask."""

    def borrow_restore(a, b):
        cur = a - b  # transient underflow by design
        under = cur >> jnp.uint64(63)
        return cur + (under << jnp.uint64(30))

    norm = Ival(0, (1 << 30) - 1)
    wraps = (
        Wrap("sub", "test_ranges.py::borrow_restore"),
        Wrap("add", "test_ranges.py::borrow_restore", bound=(1 << 30) - 1),
    )
    outs, interp = _run(
        borrow_restore, [norm, norm],
        _sds((4,), jnp.uint64), _sds((4,), jnp.uint64), wraps=wraps,
    )
    assert interp.events == []
    assert int(np.max(np.asarray(outs[0].hi))) == (1 << 30) - 1


# ------------------------------------------------------------ pjit nesting


def test_intervals_flow_through_jit_boundaries():
    @jax.jit
    def inner(a):
        return a * a

    def outer(a):
        return inner(a) + 1

    outs, interp = _run(outer, [Ival(0, 100)], _sds((4,), jnp.uint64))
    assert interp.events == []
    assert int(np.max(np.asarray(outs[0].hi))) == 10001


def test_domain_seed_mismatch_is_loud():
    def f(a, b):
        return a + b

    closed = jax.make_jaxpr(f)(_sds((4,), jnp.uint32), _sds((4,), jnp.uint32))
    with pytest.raises(ValueError, match="domain seed mismatch"):
        RangeInterp().run(closed, [Ival(0, 1)])
