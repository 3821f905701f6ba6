"""Request waterfall: per-stage latency attribution for the serve stack.

``serve.wait_ms`` says how long a request took; it cannot say *where*
the time went — admission, batching, host prep, dispatch-queue
backpressure, device execution, or future resolution are one
undifferentiated number. Tail latency under bursty mixes is a per-stage
phenomenon: you cannot tune admission, batching, or routing against a
single p99. So every :class:`~..serve.batcher.Request` carries a
**stamp vector** — a dict of monotonic marks written as the request
crosses each pipeline boundary:

    t_submit (anchor) → admitted → queued → flush_assembled → prepped
    → dispatch_queued → device_start → device_done → resolved

The marks partition wall clock into CONTIGUOUS named stages (see
:data:`STAGES`); at resolve time each interval lands in a
``serve.stage_ms.<stage>`` histogram. Because the stages tile the
request's lifetime, the named sums cover the end-to-end wall by
construction — anything they miss (a dropped stamp on an error path, a
scheduler gap) is a first-class ``other`` stage, never silent. The
``total`` stage is the request's own e2e and the denominator for the
coverage gate in scripts/serve_bench.py.

**Cross-process merge.** Monotonic clocks do not compare across
processes, so a replica never ships absolute stamps: the serving
process stashes each request's *durations* here keyed by trace id
(:func:`stash`), the RPC layer pops them (:func:`pop`) and attaches
them to the submit reply, and the front door records only the residual
``serve.stage_ms.wire`` = client e2e − replica-reported total. The
replica's own stage histograms reach the parent via the obs delta
(obs/delta.py) like every other metric — re-observing the shipped
durations client-side would double count.

**Legs.** The ``device`` stage is one host clock round the whole of a
flush's ``_execute``: transfer, launch, device time and every piece of
host arithmetic between the device calls. :func:`leg` splits it where
the work happens: a leg is an ``obs.span`` (registry aggregate, parent,
trace ids, and the ``TraceAnnotation`` that puts it on the profiler's
clock) whose milliseconds are ALSO added to the ledger of the flush the
calling thread is executing (:func:`open_flush` /
:func:`close_flush`, the dispatch thread's). At resolve the flush's
legs ride every member request into ``serve.stage_ms.device.<leg>``,
and what they do not cover is ``serve.stage_ms.device.other``, as
``other`` is for the request. Legs are flush-granular (never one an
item, a lane or a coefficient) and do not nest: a leg opened inside
another is a plain span and the outer one bills the flush, so the legs
and ``device.other`` add up to the device stage. Outside a flush a leg
is a plain span. :func:`current_leg` names the innermost leg open on
the calling thread, for the compile listener (obs/xprof.py).

Everything here is allocation-light and never raises; with
``ETH_SPECS_OBS=0`` the histogram writes are no-ops (marks still cost
one ``time.monotonic`` — the serve layer is not jit-reachable).
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

# marks in pipeline order, AFTER the t_submit anchor; "admitted" is
# written by the admission controller, the rest by batcher/service
MARKS = (
    "admitted",
    "queued",
    "flush_assembled",
    "prepped",
    "dispatch_queued",
    "device_start",
    "device_done",
    "resolved",
)

# contiguous named stages: (stage, start mark, end mark); "t0" is the
# request's t_submit. The admit stage absorbs Request construction and
# the batcher enqueue on purpose — sub-microsecond slivers between
# "admitted" and "queued" belong to admission's bill, not to "other".
STAGES = (
    ("admit", "t0", "queued"),
    ("queue", "queued", "flush_assembled"),
    ("prep", "flush_assembled", "prepped"),
    ("handoff", "prepped", "dispatch_queued"),
    ("dispatch_wait", "dispatch_queued", "device_start"),
    ("device", "device_start", "device_done"),
    ("resolve", "device_done", "resolved"),
)

STAGE_NAMES = tuple(s for s, _, _ in STAGES)

# cross-process duration stash: trace_id -> durations dict, bounded so
# a direct-service caller that never pops (serve_bench default mode)
# cannot grow it without limit
_STASH_CAP = 4096
_STASH_LOCK = threading.Lock()
_STASH: OrderedDict[str, dict] = OrderedDict()


def _reinit_lock_after_fork_in_child() -> None:
    # same idiom as obs/flight.py: a parent thread may hold the stash
    # lock at fork time; the child is single-threaded here
    global _STASH_LOCK
    _STASH_LOCK = threading.Lock()


os.register_at_fork(after_in_child=_reinit_lock_after_fork_in_child)


# ------------------------------------------------------------------- marks --


def mark(stamps: dict | None, name: str, t: float | None = None) -> None:
    """Write one monotonic mark into a request's stamp vector. First
    write wins — a hedged or retried path can never rewind a stamp, so
    the vector stays monotone even when two threads race a boundary."""
    if stamps is None:
        return
    if name not in stamps:
        stamps[name] = time.monotonic() if t is None else t


def mark_all(reqs, name: str) -> None:
    """Stamp a shared boundary (flush assembly, device start/done) onto
    every request of a flush with ONE clock read — the flush executes as
    a unit, so its members share the boundary by definition."""
    t = time.monotonic()
    for r in reqs:
        mark(getattr(r, "stamps", None), name, t)


def stage_durations_ms(t0: float, stamps: dict | None) -> dict:
    """Fold a stamp vector into named-stage durations (milliseconds).

    Returns ``{}`` until the request is resolved. A stage whose marks
    are missing (error path resolved before dispatch) is simply absent;
    its time shows up in ``other`` = total − sum(named), clamped at 0.
    """
    if not stamps:
        return {}
    resolved = stamps.get("resolved")
    if resolved is None:
        return {}
    marks = dict(stamps)
    marks["t0"] = t0
    total = max((resolved - t0) * 1e3, 0.0)
    out: dict = {}
    named = 0.0
    for stage, start, end in STAGES:
        a = marks.get(start)
        b = marks.get(end)
        if a is None or b is None:
            continue
        d = max((b - a) * 1e3, 0.0)
        out[stage] = d
        named += d
    out["other"] = max(total - named, 0.0)
    out["total"] = total
    return out


def add_legs(durations: dict, legs: dict | None) -> None:
    """Split a resolved request's device stage by the ledger of the flush
    that served it: each leg as ``device.<leg>``, the rest of the stage
    as ``device.other``, clamped at 0. No device stage (an error path),
    no split."""
    if legs is None or "device" not in durations:
        return
    for name, ms in legs.items():
        durations[f"device.{name}"] = ms
    durations["device.other"] = max(durations["device"] - sum(legs.values()), 0.0)


def observe(durations: dict) -> None:
    """Record one request's stage durations into the
    ``serve.stage_ms.<stage>`` histograms. No-op when obs is disabled
    or the request never produced durations."""
    if not durations:
        return
    from .registry import get_registry, obs_enabled

    if not obs_enabled():
        return
    reg = get_registry()
    for stage, ms in durations.items():
        reg.observe(f"serve.stage_ms.{stage}", ms)


# -------------------------------------------------------------------- legs --

# per thread: `flush` is the ledger of the flush this thread is executing
# (None outside one), `open` the names of the legs open on it
_TLS = threading.local()


def open_flush() -> dict:
    """Start the ledger of the flush the calling thread is about to
    execute and return it: leg name -> milliseconds, summed over the
    flush (a block that bisects runs its RLC legs eleven times)."""
    _TLS.flush = ledger = {}
    return ledger


def close_flush() -> None:
    _TLS.flush = None


def _open_legs() -> list:
    legs = getattr(_TLS, "open", None)
    if legs is None:
        legs = _TLS.open = []
    return legs


def current_leg() -> str | None:
    """The innermost leg open on the calling thread."""
    legs = _open_legs()
    return legs[-1] if legs else None


class _Leg:
    __slots__ = ("_span",)

    def __init__(self, span):
        self._span = span

    def __enter__(self):
        _open_legs().append(self._span.name)
        # the span itself: `.result` assigned in the body makes the exit
        # block on the device, as for any obs.span
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb):
        try:
            return self._span.__exit__(exc_type, exc, tb)
        finally:
            legs = _open_legs()
            legs.pop()
            ledger = getattr(_TLS, "flush", None)
            if ledger is not None and not legs:
                name = self._span.name
                ledger[name] = ledger.get(name, 0.0) + self._span.seconds * 1e3


def leg(name: str, **attrs):
    """A named piece of a flush's device stage, around the code that
    does the work (see the module doc). ``with leg("x") as sp`` yields
    the span, so ``sp.result = value`` blocks the exit on the device."""
    from .registry import get_registry, obs_enabled

    span = get_registry().span(name, **attrs)
    # ETH_SPECS_OBS=0: the registry's _NullSpan, no ledger, no stack
    return _Leg(span) if obs_enabled() else span


# ------------------------------------------------------------------- stash --


def stash(trace_id: str | None, durations: dict) -> None:
    """Park a resolved request's durations for the RPC layer to attach
    to its reply (keyed by trace id — ``trace.child`` preserves it, so
    the service-side request and the wire frame share the key)."""
    if not trace_id or not durations:
        return
    with _STASH_LOCK:
        _STASH[trace_id] = durations
        _STASH.move_to_end(trace_id)
        while len(_STASH) > _STASH_CAP:
            _STASH.popitem(last=False)


def pop(trace_id: str | None) -> dict | None:
    """Claim (and remove) the stashed durations for one trace id."""
    if not trace_id:
        return None
    with _STASH_LOCK:
        return _STASH.pop(trace_id, None)


def stash_size() -> int:
    with _STASH_LOCK:
        return len(_STASH)


def reset_for_tests() -> None:
    with _STASH_LOCK:
        _STASH.clear()


# ------------------------------------------------------------------ report --


def report(snapshot: dict) -> dict:
    """Waterfall summary from a registry snapshot: per-stage
    count/p50/p99/sum plus the two gateable aggregates —

    * ``coverage``: sum of named-stage milliseconds over the ``total``
      stage's milliseconds (the ≥0.95 serve_bench gate);
    * ``other_share_p50``: the ``other`` stage's p50 as a fraction of
      the ``total`` p50 (the <0.20 gate).

    Works on any snapshot with the stage histograms — a live registry,
    a merged front-door view, or a postmortem bundle's ``registry``.
    """
    hists = snapshot.get("histograms", {})
    prefix = "serve.stage_ms."
    stages: dict = {}
    for name, h in hists.items():
        if name.startswith(prefix):
            stages[name[len(prefix):]] = {
                "count": h.get("count", 0),
                "p50_ms": h.get("p50", 0.0),
                "p99_ms": h.get("p99", 0.0),
                "sum_ms": h.get("sum", 0.0),
            }
    total = stages.get("total")
    named_sum = sum(
        s["sum_ms"] for name, s in stages.items() if name in STAGE_NAMES
    )
    coverage = None
    other_share_p50 = None
    if total and total["sum_ms"] > 0:
        coverage = named_sum / total["sum_ms"]
        if total["p50_ms"] > 0:
            other = stages.get("other", {"p50_ms": 0.0})
            other_share_p50 = other["p50_ms"] / total["p50_ms"]
    return {
        "stages": stages,
        "coverage": coverage,
        "other_share_p50": other_share_p50,
        "e2e_p50_ms": total["p50_ms"] if total else None,
        "e2e_p99_ms": total["p99_ms"] if total else None,
    }
