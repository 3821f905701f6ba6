"""Interval abstract interpretation over jaxprs — the rangelint core.

The limb kernels emulate 381-bit field arithmetic in u64 lanes, and their
soundness rests on hand-reasoned magnitude bounds ("a column of 13 such
products plus carries stays under 2^64", ops/limb_field.py). This module
machine-checks those bounds: every jaxpr variable gets an integer interval
``[lo, hi]`` (exact python-int arithmetic — never numpy wraparound),
seeded from the input domains the kernel registry declares, and propagated
through transfer functions for every primitive the registered kernels
emit. Intervals are ELEMENTWISE where it matters (lo/hi are object-dtype
numpy arrays broadcast to the aval shape) because limb arrays have
per-limb bounds — the top limb of a value < 2p is ~2^22, not 2^30, and
several proofs (the lazy ``sub`` lend path) need that precision.

Loops:

* ``lax.scan``/``while`` bodies are checked for an INDUCTIVE carry
  interval: seed with the init interval, run the body, require
  out ⊆ in; otherwise join-and-retry up to
  ``ETH_SPECS_ANALYSIS_RANGE_WIDEN_STEPS`` times (converging carry
  recurrences like ``carry = (col + carry) >> 30`` stabilize in 2-4
  joins).
* A scan whose carries will not stabilize but whose xs are CONCRETE
  (e.g. the Montgomery reduction's ``scan(red_step, t, arange(13))``) is
  UNROLLED with per-iteration concrete indices, making every
  dynamic_slice position static — this is what lets the analyzer
  reproduce the schoolbook-column proof exactly.
* Anything else widens the unstable carries to dtype-top, emits a
  ``widened`` event (a lane-overflow finding: the loop is unproven), and
  continues.

Sanctioned wraparound is declared per primitive site (``Wrap``): an
arithmetic result exceeding the dtype at a matched site is clamped into
``[0, min(hi, bound, dtype_max)]`` with no event — sha256's mod-2^32
adds, the borrow-chain subtractions whose transient underflow is
restored two ops later, and the lazy ``sub`` lend path are the sanctioned
sites. Everything else that can exceed the lane fires an ``overflow``
event and the value becomes TAINTED dtype-top; masking a tainted value
with a low-bit mask fires ``masked-taint`` (the mask-consistency rule:
masks may truncate only bits the interval proves are separately-carried
high bits, never an unproven magnitude).

No execution, no compile: the interpreter walks ``jax.make_jaxpr``
output only. Wrap sites are matched on ``file.py::function`` substrings
from each eqn's user traceback — line-free, stable across edits.
"""

from __future__ import annotations

import math
import os
import time
from dataclasses import dataclass

import numpy as np

__all__ = [
    "Domain",
    "Wrap",
    "Ival",
    "Event",
    "RangeInterp",
    "ival_binop",
    "widen_steps_default",
    "range_timeout_s",
]


def widen_steps_default() -> int:
    # 12, not a tight 4-6: sha256's compress rotates its 8 registers, so
    # a widened interval takes up to 8 joins to propagate around the
    # a..h ring before the carry tuple stabilizes (measured: the mesh
    # merkle body stabilizes at join 9; plain carry recurrences at 2-4)
    raw = os.environ.get("ETH_SPECS_ANALYSIS_RANGE_WIDEN_STEPS", "")
    try:
        return max(1, int(raw)) if raw else 12
    except ValueError:
        return 12


def range_timeout_s() -> float:
    raw = os.environ.get("ETH_SPECS_ANALYSIS_RANGE_TIMEOUT_S", "")
    try:
        return float(raw) if raw else 300.0
    except ValueError:
        return 300.0


# Scans that fail to stabilize are unrolled only up to this trip count
# (the Montgomery reductions are 9-15 steps; nothing legitimate is big).
UNROLL_MAX = 128
# concrete constants above this element count are not tracked (memory)
_CONC_MAX_ELEMS = 1 << 16


# ------------------------------------------------------------ declarations --


@dataclass(frozen=True)
class Domain:
    """Declared input domain for one argument (or one pytree leaf).

    ``hi`` is an inclusive elementwise bound: an int, or an ndarray
    broadcastable against the trailing axes of the leaf (a per-limb cap
    for limb arrays). ``corners`` are VALID concrete boundary members of
    the domain — ``(label, array-or-scalar)`` pairs broadcastable the
    same way — so the declaration that seeds the prover also generates
    the runtime boundary-value tests (tests/test_range_domains.py)."""

    name: str
    hi: object
    lo: object = 0
    corners: tuple = ()


@dataclass(frozen=True)
class Wrap:
    """One sanctioned-wraparound (or trusted-bound) primitive site.

    ``site`` is a ``"file.py::function"`` substring matched against the
    eqn's user traceback frames. On an out-of-dtype interval at a
    matched eqn the result is clamped to ``[0, min(hi, bound,
    dtype_max)]`` instead of firing lane-overflow — per-site, reviewed,
    never blanket. ``bound`` (optional) additionally caps the clamped
    hi: a declared invariant for sites whose true bound the interval
    cannot derive relationally (the borrow-restore add)."""

    prim: str
    site: str
    bound: int | None = None


# ------------------------------------------------------------------ domain --


def _is_arr(x) -> bool:
    return isinstance(x, np.ndarray)


def _amin(x) -> int:
    return int(x.min()) if _is_arr(x) else int(x)


def _amax(x) -> int:
    return int(x.max()) if _is_arr(x) else int(x)


def _obj(x, shape):
    """Broadcast an int or array bound to ``shape`` as an object ndarray
    of python ints (NEVER numpy scalars — they wrap)."""
    if _is_arr(x):
        a = x if x.dtype == object else x.astype(object)
    else:
        a = np.asarray(int(x), dtype=object)
    return np.broadcast_to(a, shape)


class Ival:
    """Interval [lo, hi] of python ints; lo/hi are an int (uniform over
    the array) or an object ndarray broadcast to the var's shape.
    ``tainted`` marks values whose magnitude the analysis does NOT know
    (widened loops, unhandled primitives, unsanctioned overflow)."""

    __slots__ = ("lo", "hi", "tainted")

    def __init__(self, lo, hi, tainted: bool = False):
        # 0-d arrays collapse to ints (uniform): scalar vars stay cheap
        # and never hit array-vs-scalar broadcast mismatches
        if _is_arr(lo) and lo.ndim == 0:
            lo = int(lo[()])
        if _is_arr(hi) and hi.ndim == 0:
            hi = int(hi[()])
        self.lo = lo
        self.hi = hi
        self.tainted = tainted

    def __repr__(self):
        return f"Ival[{_amin(self.lo)}, {_amax(self.hi)}{'T' if self.tainted else ''}]"

    def broadcast(self, shape) -> "Ival":
        try:
            lo = _obj(self.lo, shape) if _is_arr(self.lo) else self.lo
            hi = _obj(self.hi, shape) if _is_arr(self.hi) else self.hi
            return Ival(lo, hi, self.tainted)
        except ValueError:
            # shape mismatch (e.g. per-shard vs global): collapse, stay sound
            return Ival(_amin(self.lo), _amax(self.hi), self.tainted)


def _binmap(a, b, f):
    """Elementwise f over int-or-ndarray bounds (object arrays hold
    python ints, so arithmetic is exact arbitrary precision)."""
    if not _is_arr(a) and not _is_arr(b):
        return f(int(a), int(b))
    return f(np.asarray(a, dtype=object), np.asarray(b, dtype=object))


def _unimap(a, f):
    if not _is_arr(a):
        return f(int(a))
    return np.frompyfunc(f, 1, 1)(np.asarray(a, dtype=object))


def ival_join(a: Ival, b: Ival) -> Ival:
    return Ival(
        _binmap(a.lo, b.lo, lambda x, y: np.minimum(x, y) if _is_arr(x) else min(x, y)),
        _binmap(a.hi, b.hi, lambda x, y: np.maximum(x, y) if _is_arr(x) else max(x, y)),
        a.tainted or b.tainted,
    )


def ival_leq(a: Ival, b: Ival) -> bool:
    """a ⊆ b (a contained in b) — the inductiveness check."""
    if a.tainted and not b.tainted:
        return False
    lo_ok = _binmap(a.lo, b.lo, lambda x, y: x >= y)
    hi_ok = _binmap(a.hi, b.hi, lambda x, y: x <= y)
    lo_ok = bool(np.all(lo_ok)) if _is_arr(lo_ok) else bool(lo_ok)
    hi_ok = bool(np.all(hi_ok)) if _is_arr(hi_ok) else bool(hi_ok)
    return lo_ok and hi_ok


def _dtype_range(dtype) -> tuple[int, int] | None:
    """(min, max) for integer/bool dtypes; None for floats (unchecked)."""
    kind = dtype.kind
    if kind == "b":
        return (0, 1)
    if kind == "u":
        return (0, (1 << (dtype.itemsize * 8)) - 1)
    if kind == "i":
        bits = dtype.itemsize * 8
        return (-(1 << (bits - 1)), (1 << (bits - 1)) - 1)
    return None


def _top(dtype, tainted: bool = False) -> Ival:
    rng = _dtype_range(np.dtype(dtype))
    if rng is None:
        return Ival(0, 0, tainted)  # floats: not range-checked
    return Ival(rng[0], rng[1], tainted)


def _conc_to_obj(arr: np.ndarray) -> np.ndarray:
    """Concrete numpy values -> object array of python ints (bool->int)."""
    if arr.dtype.kind == "b":
        arr = arr.astype(np.int64)
    if arr.dtype.kind == "f":
        # float constants are not range-relevant; track magnitude 0
        return np.zeros(arr.shape, dtype=object)
    return np.frompyfunc(int, 1, 1)(arr) if arr.ndim else np.asarray(int(arr), object)


def ival_binop(prim: str, a: Ival, b: Ival, dtype=None):
    """The pure add/sub/mul/shift/and/or/xor transfer functions, exposed
    for unit tests. Returns the RAW (unclamped) interval — overflow
    classification against ``dtype`` happens in the interpreter."""
    if prim == "add":
        return Ival(_binmap(a.lo, b.lo, lambda x, y: x + y),
                    _binmap(a.hi, b.hi, lambda x, y: x + y),
                    a.tainted or b.tainted)
    if prim == "sub":
        return Ival(_binmap(a.lo, b.hi, lambda x, y: x - y),
                    _binmap(a.hi, b.lo, lambda x, y: x - y),
                    a.tainted or b.tainted)
    if prim == "mul":
        if _amin(a.lo) >= 0 and _amin(b.lo) >= 0:
            return Ival(_binmap(a.lo, b.lo, lambda x, y: x * y),
                        _binmap(a.hi, b.hi, lambda x, y: x * y),
                        a.tainted or b.tainted)
        cs = [_binmap(x, y, lambda p, q: p * q)
              for x in (a.lo, a.hi) for y in (b.lo, b.hi)]
        lo = cs[0]
        hi = cs[0]
        for c in cs[1:]:
            lo = _binmap(lo, c, lambda x, y: np.minimum(x, y) if _is_arr(x) else min(x, y))
            hi = _binmap(hi, c, lambda x, y: np.maximum(x, y) if _is_arr(x) else max(x, y))
        return Ival(lo, hi, a.tainted or b.tainted)
    if prim == "and":
        if _amin(a.lo) >= 0 and _amin(b.lo) >= 0:
            return Ival(0, _binmap(a.hi, b.hi, lambda x, y: np.minimum(x, y) if _is_arr(x) else min(x, y)),
                        a.tainted or b.tainted)
        return _top(np.dtype(dtype) if dtype is not None else np.dtype(np.int64),
                    a.tainted or b.tainted)
    if prim in ("or", "xor"):
        if _amin(a.lo) >= 0 and _amin(b.lo) >= 0:
            # x|y <= x+y and x^y <= x+y for nonneg; never exceeds dtype
            hi = _binmap(a.hi, b.hi, lambda x, y: x + y)
            if dtype is not None:
                rng = _dtype_range(np.dtype(dtype))
                if rng is not None:
                    hi = _binmap(hi, rng[1], lambda x, y: np.minimum(x, y) if _is_arr(x) else min(x, y))
            lo = 0 if prim == "xor" else _binmap(
                a.lo, b.lo, lambda x, y: np.maximum(x, y) if _is_arr(x) else max(x, y))
            return Ival(lo, hi, a.tainted or b.tainted)
        return _top(np.dtype(dtype) if dtype is not None else np.dtype(np.int64),
                    a.tainted or b.tainted)
    if prim == "shift_right_logical":
        smin, smax = max(_amin(b.lo), 0), max(_amax(b.hi), 0)
        if _amin(a.lo) < 0:
            # logical shift reinterprets the bit pattern: a negative input
            # becomes (x mod 2^bits) >> s, a huge positive — cover it
            bits = 8 * np.dtype(dtype).itemsize if dtype is not None else 64
            return Ival(0, ((1 << bits) - 1) >> smin, a.tainted)
        return Ival(_unimap(a.lo, lambda x: x >> smax),
                    _unimap(a.hi, lambda x: x >> smin),
                    a.tainted)
    if prim == "shift_right_arithmetic":
        smin, smax = max(_amin(b.lo), 0), max(_amax(b.hi), 0)
        # negative values move TOWARD zero as the shift grows, so the
        # extreme shift amount flips with the operand's sign
        return Ival(_unimap(a.lo, lambda x: x >> (smin if x < 0 else smax)),
                    _unimap(a.hi, lambda x: x >> (smax if x < 0 else smin)),
                    a.tainted)
    if prim == "shift_left":
        bits = 8 * np.dtype(dtype).itemsize if dtype is not None else 64
        smin = min(max(_amin(b.lo), 0), bits + 8)
        smax = min(max(_amax(b.hi), 0), bits + 8)
        # negative values move AWAY from zero as the shift grows
        return Ival(_unimap(a.lo, lambda x: x << (smax if x < 0 else smin)),
                    _unimap(a.hi, lambda x: x << (smin if x < 0 else smax)),
                    a.tainted or b.tainted)
    if prim == "max":
        return Ival(_binmap(a.lo, b.lo, lambda x, y: np.maximum(x, y) if _is_arr(x) else max(x, y)),
                    _binmap(a.hi, b.hi, lambda x, y: np.maximum(x, y) if _is_arr(x) else max(x, y)),
                    a.tainted or b.tainted)
    if prim == "min":
        return Ival(_binmap(a.lo, b.lo, lambda x, y: np.minimum(x, y) if _is_arr(x) else min(x, y)),
                    _binmap(a.hi, b.hi, lambda x, y: np.minimum(x, y) if _is_arr(x) else min(x, y)),
                    a.tainted or b.tainted)
    if prim == "div":
        amag = max(abs(_amin(a.lo)), abs(_amax(a.hi)))
        if _amin(b.lo) < 0 or _amin(a.lo) < 0:
            # a possibly-negative divisor flips the quotient's sign
            # (x // -1 = -x); |b| >= 1 bounds the magnitude by |a|
            return Ival(-amag, amag, a.tainted or b.tainted)
        dlo = max(_amin(b.lo), 1)
        dhi = max(_amax(b.hi), 1)
        return Ival(_unimap(a.lo, lambda x: x // dhi),
                    _unimap(a.hi, lambda x: x // dlo), a.tainted or b.tainted)
    if prim == "rem":
        if _amin(a.lo) >= 0 and _amin(b.lo) >= 0:
            dhi = max(_amax(b.hi), 1)
            hi = _binmap(a.hi, dhi - 1, lambda x, y: np.minimum(x, y) if _is_arr(x) else min(x, y))
            return Ival(0, hi, a.tainted or b.tainted)
        # |rem| < |divisor| (sign follows the dividend) and |rem| <= |a|
        dmag = max(abs(_amin(b.lo)), abs(_amax(b.hi)), 1)
        amag = max(abs(_amin(a.lo)), abs(_amax(a.hi)))
        m = min(dmag - 1, amag)
        return Ival(-m, m, a.tainted or b.tainted)
    raise KeyError(prim)


# ------------------------------------------------------------------ events --


@dataclass(frozen=True)
class Event:
    kind: str  # "overflow" | "masked-taint" | "widened" | "unhandled"
    prim: str
    site: str  # innermost project frame "file.py::function"
    message: str

    @property
    def detail(self) -> str:
        return f"{self.prim}@{self.site}"


class AnalysisTimeout(Exception):
    """Per-variant budget exhausted — the kernel remains unproven."""


# ------------------------------------------------------------- interpreter --


class RangeInterp:
    """One interpreter per (kernel family, variant): carries the wrap
    declarations, the widening budget and the deadline."""

    def __init__(self, wraps: tuple = (), widen_steps: int | None = None,
                 deadline: float | None = None):
        self.wraps = tuple(wraps)
        self.widen_steps = widen_steps or widen_steps_default()
        self.deadline = deadline
        self.events: list[Event] = []
        self._muted = 0
        self.stats = {"eqns": 0, "unrolled_scans": 0, "widened_loops": 0,
                      "wrap_hits": 0, "unhandled": {}}
        self._frame_cache: dict[int, tuple[str, ...]] = {}

    # -- events ------------------------------------------------------------

    def _emit(self, kind, prim, frames, message):
        if self._muted:
            return
        site = frames[0] if frames else "?"
        self.events.append(Event(kind, prim, site, message))

    class _Mute:
        def __init__(self, interp):
            self.interp = interp

        def __enter__(self):
            self.interp._muted += 1

        def __exit__(self, *exc):
            self.interp._muted -= 1

    def _mute(self):
        return RangeInterp._Mute(self)

    # -- source info -------------------------------------------------------

    def _frames(self, eqn) -> tuple[str, ...]:
        si = eqn.source_info
        tb = getattr(si, "traceback", None)
        key = id(tb)
        hit = self._frame_cache.get(key)
        if hit is not None:
            return hit
        frames: list[str] = []
        try:
            from jax._src import source_info_util

            for fr in source_info_util.user_frames(tb):
                base = os.path.basename(fr.file_name)
                # the bare name: a nested function's frame carries its
                # qualified one (outer.<locals>.inner), sites are declared
                # by the function that holds the line
                frames.append(f"{base}::{fr.function_name.rsplit('.', 1)[-1]}")
        except Exception:
            pass
        out = tuple(frames)
        self._frame_cache[key] = out
        return out

    def _wrap_for(self, prim: str, frames) -> Wrap | None:
        for w in self.wraps:
            if w.prim != prim:
                continue
            for fr in frames:
                if w.site in fr:
                    return w
        return None

    # -- entry -------------------------------------------------------------

    def run(self, closed, in_ivals: list[Ival]) -> list[Ival]:
        """Analyze a ClosedJaxpr given intervals for its flat invars."""
        jaxpr = closed.jaxpr
        env: dict = {}
        conc: dict = {}
        for cv, cval in zip(jaxpr.constvars, closed.consts):
            arr = np.asarray(cval)
            if arr.dtype.kind in "iub" and arr.size <= _CONC_MAX_ELEMS:
                o = _conc_to_obj(arr)
                env[cv] = Ival(o, o)
                conc[cv] = arr
            else:
                env[cv] = self._const_ival(arr)
        if len(in_ivals) != len(jaxpr.invars):
            raise ValueError(
                f"domain seed mismatch: {len(in_ivals)} intervals for "
                f"{len(jaxpr.invars)} jaxpr inputs"
            )
        for v, iv in zip(jaxpr.invars, in_ivals):
            env[v] = self._fit(iv, v)
        self._run_eqns(jaxpr, env, conc)
        return [self._read(env, conc, v) for v in jaxpr.outvars]

    def _const_ival(self, arr: np.ndarray) -> Ival:
        if arr.dtype.kind in "iub":
            if arr.size <= _CONC_MAX_ELEMS:
                o = _conc_to_obj(arr)
                return Ival(o, o)
            return Ival(int(arr.min()), int(arr.max()))
        return Ival(0, 0)

    def _read(self, env, conc, v) -> Ival:
        from jax._src.core import Literal

        if isinstance(v, Literal):
            arr = np.asarray(v.val)
            return self._const_ival(arr)
        iv = env.get(v)
        if iv is None:
            return _top(v.aval.dtype, tainted=True)
        return iv

    def _read_conc(self, env, conc, v):
        from jax._src.core import Literal

        if isinstance(v, Literal):
            arr = np.asarray(v.val)
            if arr.dtype.kind in "iub":
                return arr
            return None
        hit = conc.get(v)
        if hit is not None:
            return hit
        # an EXACT interval (lo == hi elementwise) IS a concrete value —
        # this is how arange/iota constants survive pjit/scan/while
        # boundaries and let failed-widening scans unroll precisely
        iv = env.get(v)
        if iv is None or iv.tainted:
            return None
        dt = np.dtype(v.aval.dtype)
        if dt.kind not in "iub":
            return None
        shape = tuple(v.aval.shape)
        if math.prod(shape) > _CONC_MAX_ELEMS:
            return None
        lo, hi = iv.lo, iv.hi
        if not _is_arr(lo) and not _is_arr(hi):
            if int(lo) != int(hi):
                return None
            vals = _obj(lo, shape)
        else:
            lo_b, hi_b = _obj(lo, shape), _obj(hi, shape)
            if lo_b is not hi_b and not np.array_equal(lo_b, hi_b):
                return None
            vals = lo_b
        try:
            out = vals.astype(dt) if shape else np.asarray(int(_amin(lo)), dtype=dt)
        except (OverflowError, TypeError, ValueError):
            return None
        conc[v] = out
        return out

    # -- main eqn loop -----------------------------------------------------

    def _run_eqns(self, jaxpr, env, conc):
        for eqn in jaxpr.eqns:
            self.stats["eqns"] += 1
            if self.deadline is not None and self.stats["eqns"] % 256 == 0:
                if time.monotonic() > self.deadline:
                    raise AnalysisTimeout()
            self._eval_eqn(eqn, env, conc)

    def _eval_eqn(self, eqn, env, conc):
        prim = eqn.primitive.name
        ins = [self._read(env, conc, v) for v in eqn.invars]
        cins = [self._read_conc(env, conc, v) for v in eqn.invars]
        handler = _HANDLERS.get(prim)
        if handler is None:
            for ov in eqn.outvars:
                env[ov] = _top(ov.aval.dtype, tainted=True)
            self.stats["unhandled"][prim] = self.stats["unhandled"].get(prim, 0) + 1
            self._emit("unhandled", prim, self._frames(eqn),
                       f"no transfer function for primitive {prim}")
            return
        outs, couts = handler(self, eqn, ins, cins)
        for i, ov in enumerate(eqn.outvars):
            iv = outs[i] if i < len(outs) else _top(ov.aval.dtype, tainted=True)
            env[ov] = self._fit(iv, ov)
        if couts:
            for i, ov in enumerate(eqn.outvars):
                c = couts[i] if i < len(couts) else None
                if c is not None and c.size <= _CONC_MAX_ELEMS:
                    conc[ov] = c

    @staticmethod
    def _fit(iv: Ival, var) -> Ival:
        """Every env entry's bound arrays must broadcast against the
        var's aval shape; anything else collapses to its uniform bounds
        (always sound — at worst elementwise precision is lost)."""
        if not _is_arr(iv.lo) and not _is_arr(iv.hi):
            return iv
        shape = tuple(var.aval.shape)
        try:
            if _is_arr(iv.lo):
                np.broadcast_to(iv.lo, shape)
            if _is_arr(iv.hi):
                np.broadcast_to(iv.hi, shape)
            return iv
        except ValueError:
            return Ival(_amin(iv.lo), _amax(iv.hi), iv.tainted)

    # -- overflow classification -------------------------------------------

    def _finish_arith(self, eqn, iv: Ival, *, prim=None, aval=None) -> Ival:
        """Classify an arithmetic result against the output dtype:
        in-range passes through; out-of-range at a declared Wrap site is
        clamped (sanctioned); anything else fires ``overflow`` and the
        value becomes tainted dtype-top. Signed counters widen silently
        (lane-overflow is an unsigned-lane rule; i32 loop counters are
        jaxlint's x64-drift territory)."""
        aval = eqn.outvars[0].aval if aval is None else aval
        dt = np.dtype(aval.dtype)
        rng = _dtype_range(dt)
        if rng is None:
            return iv
        dmin, dmax = rng
        lo_min, hi_max = _amin(iv.lo), _amax(iv.hi)
        if lo_min >= dmin and hi_max <= dmax:
            return iv
        if dt.kind == "i" or dt.kind == "b":
            return Ival(dmin, dmax, iv.tainted)
        prim = prim or eqn.primitive.name
        frames = self._frames(eqn)
        w = self._wrap_for(prim, frames)
        if w is not None:
            self.stats["wrap_hits"] += 1
            cap = dmax if w.bound is None else min(w.bound, dmax)
            if _is_arr(iv.lo) or _is_arr(iv.hi):
                shape = tuple(aval.shape)
                lo_b, hi_b = _obj(iv.lo, shape), _obj(iv.hi, shape)
                inr = np.frompyfunc(lambda l, h: 0 <= l and h <= cap, 2, 1)(
                    lo_b, hi_b
                ).astype(bool)
                return Ival(np.where(inr, lo_b, 0), np.where(inr, hi_b, cap),
                            iv.tainted)
            if 0 <= lo_min and hi_max <= cap:
                return Ival(iv.lo, iv.hi, iv.tainted)
            return Ival(0, cap, iv.tainted)
        kindmsg = []
        if hi_max > dmax:
            kindmsg.append(f"hi {hi_max} > {dt.name} max {dmax}")
        if lo_min < dmin:
            kindmsg.append(f"lo {lo_min} underflows {dt.name}")
        self._emit(
            "overflow", prim, frames,
            f"{prim} interval [{lo_min}, {hi_max}] exceeds {dt.name} "
            f"({'; '.join(kindmsg)}) — a silent lane wraparound, not an "
            "exception; annotate the site `wraps` only if the wrap is the "
            "algorithm (sha256 mod-2^32) or declare a tighter input domain",
        )
        return Ival(dmin, dmax, True)


# ----------------------------------------------------------- prim handlers --
# Each handler returns (out_ivals, out_concs | None). ``self`` is the
# interpreter (handlers are plain functions registered in _HANDLERS).


def _shape_of(v):
    return tuple(v.aval.shape)


def _h_arith(self: RangeInterp, eqn, ins, cins):
    prim = eqn.primitive.name
    if prim == "add_any":  # transpose-of-fan-out accumulation IS an add
        prim = "add"
    out_dt = eqn.outvars[0].aval.dtype
    iv = ival_binop(prim, ins[0], ins[1], dtype=out_dt)
    iv = self._finish_arith(eqn, iv)
    c = None
    if prim in ("add", "sub", "mul") and cins[0] is not None and cins[1] is not None:
        with np.errstate(over="ignore"):
            c = {"add": np.add, "sub": np.subtract, "mul": np.multiply}[prim](
                cins[0], cins[1]
            )
        # only keep concrete results that the interval confirms exact
        if _amin(iv.lo) < 0 or iv.tainted:
            c = None
    return [iv], [c]


def _h_and(self: RangeInterp, eqn, ins, cins):
    out_dt = eqn.outvars[0].aval.dtype
    iv = ival_binop("and", ins[0], ins[1], dtype=out_dt)
    # mask-consistency: AND-ing a TAINTED value with a low-bit constant
    # mask pretends to "extract a limb" of a magnitude nothing proved
    for side, other in ((0, 1), (1, 0)):
        mask_iv = ins[side]
        if _is_arr(mask_iv.lo) or _is_arr(mask_iv.hi):
            # a broadcast constant mask reaches the eqn with an exact
            # elementwise interval — a uniform one is still THE mask,
            # and skipping it would let the taint hide under it
            lo_a = np.asarray(mask_iv.lo, dtype=object).ravel()
            hi_a = np.asarray(mask_iv.hi, dtype=object).ravel()
            if lo_a.size == 0 or hi_a.size == 0:
                continue
            if not (np.all(lo_a == lo_a[0]) and np.all(hi_a == hi_a[0])):
                continue
            mlo, mhi = int(lo_a[0]), int(hi_a[0])
        else:
            mlo, mhi = int(mask_iv.lo), int(mask_iv.hi)
        if mlo != mhi:
            continue
        m = mhi
        if m <= 0 or (m & (m + 1)) != 0:
            continue  # not a low-bit mask 2^k - 1
        if ins[other].tainted:
            self._emit(
                "masked-taint", "and", self._frames(eqn),
                f"AND with mask {hex(m)} applied to a value whose interval "
                "was widened to dtype-top — the mask truncates bits the "
                "analysis cannot prove are separately-propagated carries "
                "(an overflow upstream may be hiding under this mask)",
            )
    return [iv], None


def _h_shift(self: RangeInterp, eqn, ins, cins):
    prim = eqn.primitive.name
    out_dt = eqn.outvars[0].aval.dtype
    iv = ival_binop(prim, ins[0], ins[1], dtype=out_dt)
    if prim == "shift_left":
        iv = self._finish_arith(eqn, iv)
    return [iv], None


def _h_pass_binop(self: RangeInterp, eqn, ins, cins):
    prim = eqn.primitive.name
    out_dt = eqn.outvars[0].aval.dtype
    return [ival_binop(prim, ins[0], ins[1], dtype=out_dt)], None


def _h_cmp(self: RangeInterp, eqn, ins, cins):
    """Comparisons fold to an exact 0/1 when the intervals decide them —
    jax's negative-index normalization (``i if i >= 0 else i + n``)
    routes dynamic_slice starts through lt/select_n, and folding the
    predicate is what keeps concrete scan indices concrete."""
    if cins[0] is not None and cins[1] is not None:
        op = {"eq": np.equal, "ne": np.not_equal, "lt": np.less,
              "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal}
        c = op[eqn.primitive.name](cins[0], cins[1])
        o = _conc_to_obj(c)
        return [Ival(o, o)], [c]
    a, b = ins
    alo, ahi, blo, bhi = _amin(a.lo), _amax(a.hi), _amin(b.lo), _amax(b.hi)
    prim = eqn.primitive.name
    verdict = None
    if prim == "lt":
        verdict = 1 if ahi < blo else (0 if alo >= bhi else None)
    elif prim == "le":
        verdict = 1 if ahi <= blo else (0 if alo > bhi else None)
    elif prim == "gt":
        verdict = 1 if alo > bhi else (0 if ahi <= blo else None)
    elif prim == "ge":
        verdict = 1 if alo >= bhi else (0 if ahi < blo else None)
    elif prim == "eq":
        verdict = 0 if (ahi < blo or alo > bhi) else (
            1 if alo == ahi == blo == bhi else None
        )
    elif prim == "ne":
        verdict = 1 if (ahi < blo or alo > bhi) else (
            0 if alo == ahi == blo == bhi else None
        )
    if verdict is not None:
        return [Ival(verdict, verdict)], None
    return [Ival(0, 1)], None


def _h_not(self: RangeInterp, eqn, ins, cins):
    dt = np.dtype(eqn.outvars[0].aval.dtype)
    rng = _dtype_range(dt)
    if rng is None:
        return [Ival(0, 0)], None
    dmin, dmax = rng
    a = ins[0]
    if dt.kind in "ub" and _amin(a.lo) >= 0:
        return [Ival(_binmap(a.hi, dmax, lambda x, y: y - x),
                     _binmap(a.lo, dmax, lambda x, y: y - x), a.tainted)], None
    return [Ival(dmin, dmax, a.tainted)], None


def _h_neg(self: RangeInterp, eqn, ins, cins):
    a = ins[0]
    iv = Ival(_unimap(a.hi, lambda x: -x), _unimap(a.lo, lambda x: -x), a.tainted)
    return [self._finish_arith(eqn, iv)], None


def _h_clamp(self: RangeInterp, eqn, ins, cins):
    mn, x, mx = ins
    lo = min(max(_amin(x.lo), _amin(mn.lo)), _amin(mx.lo))
    hi = min(max(_amax(x.hi), _amax(mn.hi)), _amax(mx.hi))
    return [Ival(lo, hi, x.tainted)], None


def _h_sign(self: RangeInterp, eqn, ins, cins):
    dt = np.dtype(eqn.outvars[0].aval.dtype)
    if dt.kind == "u" or _amin(ins[0].lo) >= 0:
        return [Ival(0, 1, ins[0].tainted)], None
    return [Ival(-1, 1, ins[0].tainted)], None


def _h_select(self: RangeInterp, eqn, ins, cins):
    pred = ins[0]
    cases = ins[1:]
    # an exact uniform predicate picks ONE case — interval AND concrete
    # survive (the folded negative-index select around dynamic_slice)
    if (
        not _is_arr(pred.lo)
        and not _is_arr(pred.hi)
        and not pred.tainted
        and int(pred.lo) == int(pred.hi)
        and 0 <= int(pred.lo) < len(cases)
    ):
        k = int(pred.lo)
        return [cases[k]], [cins[1 + k]]
    out = cases[0].broadcast(_shape_of(eqn.outvars[0])) if _is_arr(cases[0].lo) else cases[0]
    for c in cases[1:]:
        out = ival_join(out, c)
    return [out], None


def _h_identity(self: RangeInterp, eqn, ins, cins):
    return [ins[0]], [cins[0]]


def _h_convert(self: RangeInterp, eqn, ins, cins):
    dt = np.dtype(eqn.outvars[0].aval.dtype)
    rng = _dtype_range(dt)
    a = ins[0]
    if rng is None:
        return [Ival(0, 0)], None
    dmin, dmax = rng
    if dt.kind == "b":
        return [Ival(0, 1, a.tainted)], None
    src_dt = np.dtype(eqn.invars[0].aval.dtype)
    if src_dt.kind == "f":
        return [Ival(dmin, dmax, a.tainted)], None
    lo_min, hi_max = _amin(a.lo), _amax(a.hi)
    if lo_min >= dmin and hi_max <= dmax:
        c = None
        if cins[0] is not None and dt.kind in "iub":
            c = cins[0].astype(dt)
        return [Ival(a.lo, a.hi, a.tainted)], [c]
    if src_dt.kind == "i" and dt.kind == "u" and lo_min < 0 and hi_max <= dmax:
        # signed->unsigned reinterpretation of a possibly-negative value
        # (two's complement, defined) — signed values are outside the
        # unsigned-lane overflow rule, so widen silently
        return [Ival(0, dmax, a.tainted)], None
    # narrowing that can truncate: a mod-2^k wrap in disguise
    return [self._finish_arith(eqn, a, prim="convert_element_type")], None


def _h_bitcast(self: RangeInterp, eqn, ins, cins):
    return [_top(eqn.outvars[0].aval.dtype)], None


def _h_iota(self: RangeInterp, eqn, ins, cins):
    aval = eqn.outvars[0].aval
    dim = eqn.params.get("dimension", 0)
    shape = tuple(aval.shape)
    n = shape[dim] if shape else 1
    if np.dtype(aval.dtype).kind in "iu" and math.prod(shape) <= _CONC_MAX_ELEMS:
        idx = np.arange(n, dtype=np.int64)
        view = idx.reshape([n if i == dim else 1 for i in range(len(shape))])
        c = np.broadcast_to(view, shape).astype(aval.dtype)
        o = _conc_to_obj(c)
        return [Ival(o, o)], [np.ascontiguousarray(c)]
    return [Ival(0, max(n - 1, 0))], None


def _materialize(iv: Ival, shape) -> tuple:
    return _obj(iv.lo, shape), _obj(iv.hi, shape)


def _h_shape_op(self: RangeInterp, eqn, ins, cins):
    """Pure layout ops: uniform intervals pass through; elementwise
    intervals are transformed positionally with numpy."""
    prim = eqn.primitive.name
    a = ins[0]
    out_shape = _shape_of(eqn.outvars[0])
    in_shape = _shape_of(eqn.invars[0])

    def xform(arr):
        p = eqn.params
        if prim == "broadcast_in_dim":
            bdims = p["broadcast_dimensions"]
            view_shape = [1] * len(out_shape)
            for i, d in enumerate(bdims):
                view_shape[d] = arr.shape[i]
            return np.broadcast_to(arr.reshape(view_shape), out_shape)
        if prim == "reshape":
            return np.reshape(np.ascontiguousarray(arr), out_shape)
        if prim == "transpose":
            return np.transpose(arr, p["permutation"])
        if prim == "squeeze":
            return np.squeeze(arr, axis=tuple(p["dimensions"]))
        if prim == "rev":
            return np.flip(arr, axis=tuple(p["dimensions"]))
        if prim == "slice":
            idx = tuple(
                slice(s, l, (st or 1))
                for s, l, st in zip(
                    p["start_indices"], p["limit_indices"],
                    p.get("strides") or [1] * len(p["start_indices"]),
                )
            )
            return arr[idx]
        if prim == "expand_dims":
            return np.reshape(np.ascontiguousarray(arr), out_shape)
        raise KeyError(prim)

    c = None
    if cins[0] is not None:
        try:
            c = np.ascontiguousarray(xform(cins[0]))
        except Exception:
            c = None
    if not _is_arr(a.lo) and not _is_arr(a.hi):
        return [a], [c]
    lo, hi = _materialize(a, in_shape)
    try:
        return [Ival(xform(lo), xform(hi), a.tainted)], [c]
    except Exception:
        return [Ival(_amin(a.lo), _amax(a.hi), a.tainted)], [c]


def _h_concat(self: RangeInterp, eqn, ins, cins):
    dim = eqn.params["dimension"]
    any_arr = any(_is_arr(i.lo) or _is_arr(i.hi) for i in ins)
    tainted = any(i.tainted for i in ins)
    c = None
    if all(x is not None for x in cins):
        try:
            c = np.concatenate(cins, axis=dim)
        except Exception:
            c = None
    if not any_arr:
        lo = min(_amin(i.lo) for i in ins)
        hi = max(_amax(i.hi) for i in ins)
        if all(_amin(i.lo) == lo and _amax(i.hi) == hi for i in ins):
            return [Ival(lo, hi, tainted)], [c]
        # differing uniform ranges: keep positional structure
        los = [np.full(_shape_of(eqn.invars[i]), _amin(v.lo), object)
               for i, v in enumerate(ins)]
        his = [np.full(_shape_of(eqn.invars[i]), _amax(v.hi), object)
               for i, v in enumerate(ins)]
        return [Ival(np.concatenate(los, axis=dim),
                     np.concatenate(his, axis=dim), tainted)], [c]
    los, his = [], []
    for i, v in enumerate(ins):
        shp = _shape_of(eqn.invars[i])
        lo, hi = _materialize(v, shp)
        los.append(lo)
        his.append(hi)
    return [Ival(np.concatenate(los, axis=dim), np.concatenate(his, axis=dim),
                 tainted)], [c]


def _h_pad(self: RangeInterp, eqn, ins, cins):
    a, padval = ins
    out_shape = _shape_of(eqn.outvars[0])
    in_shape = _shape_of(eqn.invars[0])
    config = eqn.params["padding_config"]
    tainted = a.tainted or padval.tainted
    simple = all(lo_p >= 0 and hi_p >= 0 and interior == 0
                 for lo_p, hi_p, interior in config)
    if not simple:
        lo = min(_amin(a.lo), _amin(padval.lo))
        hi = max(_amax(a.hi), _amax(padval.hi))
        return [Ival(lo, hi, tainted)], None
    if not _is_arr(a.lo) and not _is_arr(a.hi):
        if _amin(a.lo) == _amin(padval.lo) and _amax(a.hi) == _amax(padval.hi):
            return [Ival(a.lo, a.hi, tainted)], None
    lo_in, hi_in = _materialize(a, in_shape)
    target = tuple(
        slice(lo_p, lo_p + n) for (lo_p, _, _), n in zip(config, in_shape)
    )

    def build(val_arr, fill):
        arr = np.full(out_shape, fill, dtype=object)
        arr[target] = val_arr
        return arr

    return [Ival(build(lo_in, _amin(padval.lo)),
                 build(hi_in, _amax(padval.hi)), tainted)], None


def _h_gather(self: RangeInterp, eqn, ins, cins):
    op = ins[0]
    return [Ival(_amin(op.lo), _amax(op.hi), op.tainted)], None


def _h_dynamic_slice(self: RangeInterp, eqn, ins, cins):
    op = ins[0]
    out_shape = _shape_of(eqn.outvars[0])
    in_shape = _shape_of(eqn.invars[0])
    starts = cins[1:]
    if all(s is not None for s in starts) and (_is_arr(op.lo) or _is_arr(op.hi)):
        idx = []
        for i, s in enumerate(starts):
            st = int(np.asarray(s).reshape(()))
            st = max(0, min(st, in_shape[i] - out_shape[i]))
            idx.append(slice(st, st + out_shape[i]))
        lo, hi = _materialize(op, in_shape)
        c = None
        if cins[0] is not None:
            c = np.ascontiguousarray(cins[0][tuple(idx)])
        return [Ival(lo[tuple(idx)], hi[tuple(idx)], op.tainted)], [c]
    return [Ival(_amin(op.lo), _amax(op.hi), op.tainted)], None


def _h_dynamic_update_slice(self: RangeInterp, eqn, ins, cins):
    op, upd = ins[0], ins[1]
    out_shape = _shape_of(eqn.outvars[0])
    upd_shape = _shape_of(eqn.invars[1])
    starts = cins[2:]
    tainted = op.tainted or upd.tainted
    if all(s is not None for s in starts):
        idx = []
        for i, s in enumerate(starts):
            st = int(np.asarray(s).reshape(()))
            st = max(0, min(st, out_shape[i] - upd_shape[i]))
            idx.append(slice(st, st + upd_shape[i]))
        lo, hi = _materialize(op, out_shape)
        lo = np.array(lo, dtype=object)
        hi = np.array(hi, dtype=object)
        ulo, uhi = _materialize(upd, upd_shape)
        lo[tuple(idx)] = ulo
        hi[tuple(idx)] = uhi
        return [Ival(lo, hi, tainted)], None
    # unknown position: every element is either old or SOME update value
    joined = ival_join(
        op.broadcast(out_shape) if _is_arr(op.lo) else op,
        Ival(_amin(upd.lo), _amax(upd.hi), upd.tainted),
    )
    return [joined], None


def _h_scatter(self: RangeInterp, eqn, ins, cins):
    op, _idx, upd = ins[0], ins[1], ins[2]
    out_shape = _shape_of(eqn.outvars[0])
    joined = ival_join(
        op.broadcast(out_shape) if _is_arr(op.lo) else op,
        Ival(_amin(upd.lo), _amax(upd.hi), upd.tainted),
    )
    return [joined], None


def _h_scatter_add(self: RangeInterp, eqn, ins, cins):
    op, _idx, upd = ins[0], ins[1], ins[2]
    n = max(math.prod(_shape_of(eqn.invars[2])), 1)
    iv = Ival(
        _binmap(op.lo, min(_amin(upd.lo), 0) * n, lambda x, y: x + y),
        _binmap(op.hi, max(_amax(upd.hi), 0) * n, lambda x, y: x + y),
        op.tainted or upd.tainted,
    )
    return [self._finish_arith(eqn, iv, prim="add")], None


def _reduce_axes(eqn):
    return tuple(eqn.params.get("axes", ()))


def _h_reduce_minmax_like(self: RangeInterp, eqn, ins, cins):
    a = ins[0]
    axes = _reduce_axes(eqn)
    prim = eqn.primitive.name
    if not _is_arr(a.lo) and not _is_arr(a.hi):
        return [a], None
    in_shape = _shape_of(eqn.invars[0])
    lo, hi = _materialize(a, in_shape)
    if prim in ("reduce_max", "reduce_or"):
        return [Ival(np.max(lo, axis=axes), np.max(hi, axis=axes), a.tainted)], None
    return [Ival(np.min(lo, axis=axes), np.min(hi, axis=axes), a.tainted)], None


def _h_reduce_bitwise(self: RangeInterp, eqn, ins, cins):
    """reduce_or / reduce_and over INTEGER lanes: bitwise, not order —
    1|2 = 3 exceeds the elementwise max and 1&2 = 0 undershoots the
    elementwise min, so min/max transfer is unsound here. For nonneg
    values: OR only sets bits (result >= every element, bits bounded by
    the union cover 2^bits(max hi) - 1), AND only clears them
    (0 <= result <= every element)."""
    a = ins[0]
    prim = eqn.primitive.name
    dt = np.dtype(eqn.outvars[0].aval.dtype)
    if dt.kind == "b":
        # 0/1 lanes: or == max, and == min — the elementwise transfer
        # is exact
        return _h_reduce_minmax_like(self, eqn, ins, cins)
    if _amin(a.lo) < 0:
        return [_top(dt, a.tainted)], None
    axes = _reduce_axes(eqn)
    cover = lambda x: (1 << int(x).bit_length()) - 1
    if _is_arr(a.lo) or _is_arr(a.hi):
        in_shape = _shape_of(eqn.invars[0])
        lo, hi = _materialize(a, in_shape)
        if prim == "reduce_or":
            return [Ival(np.max(lo, axis=axes),
                         _unimap(np.max(hi, axis=axes), cover),
                         a.tainted)], None
        return [Ival(0, np.min(hi, axis=axes), a.tainted)], None
    if prim == "reduce_or":
        return [Ival(int(a.lo), cover(a.hi), a.tainted)], None
    return [Ival(0, int(a.hi), a.tainted)], None


def _h_reduce_sum(self: RangeInterp, eqn, ins, cins):
    a = ins[0]
    axes = _reduce_axes(eqn)
    in_shape = _shape_of(eqn.invars[0])
    if _is_arr(a.lo) or _is_arr(a.hi):
        lo, hi = _materialize(a, in_shape)
        iv = Ival(np.sum(lo, axis=axes), np.sum(hi, axis=axes), a.tainted)
    else:
        n = math.prod(in_shape[ax] for ax in axes) if axes else 1
        iv = Ival(int(a.lo) * n, int(a.hi) * n, a.tainted)
    return [self._finish_arith(eqn, iv, prim="add")], None


def _h_cumsum(self: RangeInterp, eqn, ins, cins):
    """Inclusive running sum along one axis: element k is a sum of k+1
    inputs, so the whole output lies between the axis length times the
    input's lowest (if negative) or its lowest itself, and likewise up."""
    a = ins[0]
    n = _shape_of(eqn.invars[0])[eqn.params["axis"]]
    lo, hi = int(_amin(a.lo)), int(_amax(a.hi))
    iv = Ival(min(lo, lo * n), max(hi, hi * n), a.tainted)
    return [self._finish_arith(eqn, iv, prim="add")], None


def _h_argminmax(self: RangeInterp, eqn, ins, cins):
    axes = tuple(eqn.params.get("axes", ()))
    in_shape = _shape_of(eqn.invars[0])
    n = max((in_shape[ax] for ax in axes), default=1)
    return [Ival(0, max(n - 1, 0))], None


def _h_jit(self: RangeInterp, eqn, ins, cins):
    sub = eqn.params["jaxpr"]
    outs = self.run(sub, [iv for iv in ins])
    return outs, None


def _h_closed_call(self: RangeInterp, eqn, ins, cins):
    sub = eqn.params.get("call_jaxpr") or eqn.params.get("jaxpr")
    outs = self.run(sub, [iv for iv in ins])
    return outs, None


def _h_custom_call(self: RangeInterp, eqn, ins, cins):
    sub = eqn.params.get("call_jaxpr")
    if sub is None:
        return [
            _top(ov.aval.dtype, tainted=True) for ov in eqn.outvars
        ], None
    n = len(sub.jaxpr.invars)
    outs = self.run(sub, [iv for iv in ins[:n]])
    return outs, None


def _h_shard_map(self: RangeInterp, eqn, ins, cins):
    """Enter the per-shard body. The shard split changes LEADING axes
    only, so elementwise bounds broadcastable against the per-shard aval
    (per-limb caps on the trailing limb axis — the precision the fat-p
    lend proof needs) carry straight across; anything else collapses to
    its uniform bounds. The mesh is stashed for collective axis sizes."""
    sub = eqn.params["jaxpr"]  # open Jaxpr
    mesh = eqn.params.get("mesh")
    env: dict = {}
    conc: dict = {}
    for v, iv in zip(sub.invars, ins):
        env[v] = self._fit(iv, v)
    prev_mesh = getattr(self, "_mesh", None)
    self._mesh = mesh
    try:
        self._run_eqns(sub, env, conc)
    finally:
        self._mesh = prev_mesh
    return [self._read(env, conc, v) for v in sub.outvars], None


def _mesh_axis_size(self: RangeInterp, eqn) -> int:
    mesh = getattr(self, "_mesh", None)
    names = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(names, str):
        names = (names,)
    total = 1
    if mesh is not None:
        shape = dict(getattr(mesh, "shape", {}))
        for n in names:
            total *= int(shape.get(n, 1))
    else:
        total = 8  # conservative default when the mesh is unknown
    return max(total, 1)


def _h_psum(self: RangeInterp, eqn, ins, cins):
    n = _mesh_axis_size(self, eqn)
    outs = []
    for i, iv in enumerate(ins):
        s = Ival(_amin(iv.lo) * n, _amax(iv.hi) * n, iv.tainted)
        # classify EVERY operand of a tuple psum against its own output
        # aval — a scaled-but-unchecked second operand would leak an
        # out-of-dtype interval downstream unproven
        outs.append(
            self._finish_arith(eqn, s, prim="add", aval=eqn.outvars[i].aval)
        )
    return outs, None


def _h_all_gather(self: RangeInterp, eqn, ins, cins):
    iv = ins[0]
    return [Ival(_amin(iv.lo), _amax(iv.hi), iv.tainted)], None


def _h_axis_index(self: RangeInterp, eqn, ins, cins):
    return [Ival(0, _mesh_axis_size(self, eqn) - 1)], None


# -- loops ------------------------------------------------------------------


def _reduce_leading(iv: Ival, shape) -> Ival:
    """Join an xs interval over the scan axis (axis 0)."""
    if not _is_arr(iv.lo) and not _is_arr(iv.hi):
        return iv
    lo, hi = _materialize(iv, shape)
    if lo.ndim == 0:
        return Ival(int(lo), int(hi), iv.tainted)
    return Ival(np.min(lo, axis=0), np.max(hi, axis=0), iv.tainted)


def _h_scan(self: RangeInterp, eqn, ins, cins):
    p = eqn.params
    body = p["jaxpr"]  # ClosedJaxpr
    nc, ncar = p["num_consts"], p["num_carry"]
    length = int(p["length"])
    consts_iv = ins[:nc]
    init_iv = ins[nc : nc + ncar]
    xs_iv = ins[nc + ncar :]
    xs_shapes = [_shape_of(v) for v in eqn.invars[nc + ncar :]]
    xs_step = [_reduce_leading(iv, shp) for iv, shp in zip(xs_iv, xs_shapes)]
    n_out = len(eqn.outvars)

    def run_body(carry_ivs):
        return self.run(body, list(consts_iv) + list(carry_ivs) + list(xs_step))

    # 1) inductive / widening pass (muted: transient joins must not emit)
    carry = list(init_iv)
    stable = False
    with self._mute():
        for _ in range(self.widen_steps):
            outs = run_body(carry)
            new_carry = outs[:ncar]
            if all(ival_leq(n_, c_) for n_, c_ in zip(new_carry, carry)):
                stable = True
                break
            carry = [ival_join(c_, n_) for c_, n_ in zip(carry, new_carry)]

    if stable:
        outs = run_body(carry)  # authoritative, unmuted
        # a length-0 scan never runs its body — the carry output IS
        # init, so join it in (mirrors _h_while's zero-iteration join)
        final = [
            ival_join(i_, o_) for i_, o_ in zip(init_iv, outs[:ncar])
        ] + list(outs[ncar:])
        return _scan_outs(eqn, final, ncar, n_out, length), None

    # 2) concrete unroll: per-iteration xs values make dynamic slice
    #    positions static (the Montgomery red_step proof)
    xs_conc = cins[nc + ncar :]
    if length <= UNROLL_MAX and xs_iv and all(c is not None for c in xs_conc):
        self.stats["unrolled_scans"] += 1
        carry = list(init_iv)
        ys_join: list[Ival] | None = None
        reverse = bool(p.get("reverse", False))
        order = range(length - 1, -1, -1) if reverse else range(length)
        for it in order:
            step_ins = []
            for c, shp in zip(xs_conc, xs_shapes):
                row = np.ascontiguousarray(c[it])
                o = _conc_to_obj(row)
                step_ins.append(Ival(o, o))
            # concrete xs also flow as concrete values into the body
            outs = self._run_with_conc(
                body, list(consts_iv) + list(carry) + step_ins,
                conc_tail=[np.ascontiguousarray(c[it]) for c in xs_conc],
                n_tail=len(xs_conc),
            )
            carry = outs[:ncar]
            ys = outs[ncar:]
            if ys_join is None:
                ys_join = list(ys)
            else:
                ys_join = [ival_join(a, b) for a, b in zip(ys_join, ys)]
        final = list(carry) + (ys_join or [])
        return _scan_outs(eqn, final, ncar, n_out, length), None

    # 3) widen-to-top: only the carries that failed to stabilize
    self.stats["widened_loops"] += 1
    widened = _widen_fixpoint(
        self, lambda w: run_body(w)[:ncar], carry, eqn, "scan",
        lambda i, c_: (
            f"scan carry {i} has no inductive interval within "
            f"{self.widen_steps} widening steps (init "
            f"[{_amin(init_iv[i].lo)}, {_amax(init_iv[i].hi)}] grew to "
            f"[{_amin(c_.lo)}, {_amax(c_.hi)}]) and the xs are not "
            "concrete — carry widened to dtype-top; the loop body is "
            "UNPROVEN against lane overflow"
        ),
    )
    outs = run_body(widened)  # authoritative, unmuted
    final = list(widened) + list(outs[ncar:])
    return _scan_outs(eqn, final, ncar, n_out, length), None


def _widen_fixpoint(self: RangeInterp, probe_body, carry, eqn, kind, msg):
    """Top the non-inductive carries, re-checking the survivors against
    the WIDENED environment until a fixpoint: widening one carry can
    un-stabilize a dependent one (c0 = f(c1)) that looked inductive
    before the top. Each pass tops >= 1 new carry, so <= len(carry)
    passes. Emits a 'widened' event per topped UNSIGNED carry (the
    range-checked lanes)."""
    widened = list(carry)
    topped: set = set()
    while True:
        with self._mute():
            probe = probe_body(widened)
        changed = False
        for i, (c_, n_) in enumerate(zip(widened, probe)):
            if i in topped or ival_leq(n_, c_):
                continue
            dt = np.dtype(eqn.outvars[i].aval.dtype)
            widened[i] = _top(dt, tainted=dt.kind == "u")
            topped.add(i)
            changed = True
            if dt.kind == "u":
                self._emit("widened", kind, self._frames(eqn), msg(i, c_))
        if not changed:
            break
    return widened


def _scan_outs(eqn, outs, ncar, n_out, length):
    """Map body-shaped output intervals onto the scan eqn's outvars
    (ys gain the leading length axis)."""
    result = []
    for i in range(n_out):
        iv = outs[i] if i < len(outs) else None
        ov = eqn.outvars[i]
        if iv is None:
            result.append(_top(ov.aval.dtype, tainted=True))
            continue
        if i >= ncar and (_is_arr(iv.lo) or _is_arr(iv.hi)):
            shp = _shape_of(ov)
            try:
                lo = np.broadcast_to(_obj(iv.lo, shp[1:]), shp)
                hi = np.broadcast_to(_obj(iv.hi, shp[1:]), shp)
                result.append(Ival(lo, hi, iv.tainted))
                continue
            except Exception:
                result.append(Ival(_amin(iv.lo), _amax(iv.hi), iv.tainted))
                continue
        result.append(iv)
    return result


def _h_while(self: RangeInterp, eqn, ins, cins):
    p = eqn.params
    cond_n, body_n = p["cond_nconsts"], p["body_nconsts"]
    body = p["body_jaxpr"]
    cond_consts = ins[:cond_n]
    body_consts = ins[cond_n : cond_n + body_n]
    init = ins[cond_n + body_n :]

    def run_body(carry_ivs):
        return self.run(body, list(body_consts) + list(carry_ivs))

    def run_cond(carry_ivs):
        # the condition's arithmetic runs once per iteration on device:
        # it must be checked against the SAME carry cover as the body
        self.run(p["cond_jaxpr"], list(cond_consts) + list(carry_ivs))

    carry = list(init)
    stable = False
    with self._mute():
        for _ in range(self.widen_steps):
            new_carry = run_body(carry)
            if all(ival_leq(n_, c_) for n_, c_ in zip(new_carry, carry)):
                stable = True
                break
            carry = [ival_join(c_, n_) for c_, n_ in zip(carry, new_carry)]
    if stable:
        final = run_body(carry)  # authoritative, unmuted
        run_cond(carry)  # cond arithmetic checked over the fixpoint
        joined = [ival_join(i_, f_) for i_, f_ in zip(init, final)]
        return joined, None
    self.stats["widened_loops"] += 1
    widened = _widen_fixpoint(
        self, run_body, carry, eqn, "while",
        lambda i, c_: (
            f"while carry {i} has no inductive interval within "
            f"{self.widen_steps} widening steps — widened to dtype-top; "
            "the loop body is UNPROVEN against lane overflow"
        ),
    )
    run_body(widened)  # authoritative pass for body-internal events
    run_cond(widened)
    return widened, None


def _h_cond(self: RangeInterp, eqn, ins, cins):
    branches = eqn.params["branches"]
    op_ins = ins[1:]
    joined: list[Ival] | None = None
    for br in branches:
        outs = self.run(br, list(op_ins))
        if joined is None:
            joined = list(outs)
        else:
            joined = [ival_join(a, b) for a, b in zip(joined, outs)]
    return joined or [], None


def _run_with_conc(self: RangeInterp, closed, in_ivals, conc_tail, n_tail):
    """run() but seeding concrete values for the LAST n_tail inputs
    (unrolled scan iterations)."""
    jaxpr = closed.jaxpr
    env: dict = {}
    conc: dict = {}
    for cv, cval in zip(jaxpr.constvars, closed.consts):
        arr = np.asarray(cval)
        if arr.dtype.kind in "iub" and arr.size <= _CONC_MAX_ELEMS:
            o = _conc_to_obj(arr)
            env[cv] = Ival(o, o)
            conc[cv] = arr
        else:
            env[cv] = self._const_ival(arr)
    for v, iv in zip(jaxpr.invars, in_ivals):
        env[v] = iv
    if n_tail:
        for v, c in zip(jaxpr.invars[-n_tail:], conc_tail):
            if c is not None:
                conc[v] = c
    self._run_eqns(jaxpr, env, conc)
    return [self._read(env, conc, v) for v in jaxpr.outvars]


RangeInterp._run_with_conc = _run_with_conc


_HANDLERS = {
    "add": _h_arith,
    "sub": _h_arith,
    "mul": _h_arith,
    "add_any": _h_arith,
    "and": _h_and,
    "or": _h_pass_binop,
    "xor": _h_pass_binop,
    "min": _h_pass_binop,
    "max": _h_pass_binop,
    "div": _h_pass_binop,
    "rem": _h_pass_binop,
    "shift_left": _h_shift,
    "shift_right_logical": _h_shift,
    "shift_right_arithmetic": _h_shift,
    "eq": _h_cmp,
    "ne": _h_cmp,
    "lt": _h_cmp,
    "le": _h_cmp,
    "gt": _h_cmp,
    "ge": _h_cmp,
    "not": _h_not,
    "neg": _h_neg,
    "sign": _h_sign,
    "clamp": _h_clamp,
    "select_n": _h_select,
    "select": _h_select,
    "device_put": _h_identity,
    "copy": _h_identity,
    "optimization_barrier": _h_identity,
    "stop_gradient": _h_identity,
    "convert_element_type": _h_convert,
    "bitcast_convert_type": _h_bitcast,
    "iota": _h_iota,
    "broadcast_in_dim": _h_shape_op,
    "reshape": _h_shape_op,
    "transpose": _h_shape_op,
    "squeeze": _h_shape_op,
    "expand_dims": _h_shape_op,
    "rev": _h_shape_op,
    "slice": _h_shape_op,
    "concatenate": _h_concat,
    "pad": _h_pad,
    "gather": _h_gather,
    "dynamic_slice": _h_dynamic_slice,
    "dynamic_update_slice": _h_dynamic_update_slice,
    "scatter": _h_scatter,
    "scatter-add": _h_scatter_add,
    "reduce_and": _h_reduce_bitwise,
    "reduce_or": _h_reduce_bitwise,
    "reduce_max": _h_reduce_minmax_like,
    "reduce_min": _h_reduce_minmax_like,
    "reduce_sum": _h_reduce_sum,
    "cumsum": _h_cumsum,
    "argmax": _h_argminmax,
    "argmin": _h_argminmax,
    "jit": _h_jit,
    "closed_call": _h_closed_call,
    "core_call": _h_closed_call,
    "custom_jvp_call": _h_custom_call,
    "custom_vjp_call": _h_custom_call,
    "remat_call": _h_custom_call,
    "checkpoint": _h_custom_call,
    "shard_map": _h_shard_map,
    "psum": _h_psum,
    "psum_invariant": _h_psum,
    "all_gather": _h_all_gather,
    "axis_index": _h_axis_index,
    "scan": _h_scan,
    "while": _h_while,
    "cond": _h_cond,
}
