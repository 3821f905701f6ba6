"""Declarative SLOs evaluated from a registry snapshot.

An SLO here is a small named predicate over ``obs.snapshot()`` (or an
``obs_report.json`` loaded from disk — the shapes match), so the same
objectives gate a live service (scripts/serve_bench.py), a CI run (the
obs-report job), and ad-hoc inspection. Three kinds:

  * ``quantile_max`` — a mergeable-histogram quantile must not exceed
    a bound (serve wait p99);
  * ``counter_max`` — a counter must not exceed a bound (watchdog
    divergences == 0, compiles-after-warmup == 0 are ``bound 0``);
  * ``ratio_max`` — numerator/denominator counters must not exceed a
    bound (``serve.degraded_items`` per served request — the per-ITEM
    degradation counter, not per-event ``fault.degraded``: one dead
    flush degrades every member request, and the ratio must say so).

Evaluation is vacuous-pass on missing data *except* for ratio
numerators: a nonzero numerator with a zero denominator is a violation
(degradations happened with no traffic to amortize them), and an absent
counter reads as 0 (monotonic counters start there).

The default objective set — the north-star telemetry contract — and its
env knobs:

    ETH_SPECS_SLO_WAIT_P99_MS    serve wait p99 bound, ms   (default 250)
    ETH_SPECS_SLO_DEGRADED_RATE  serve.degraded_items per serve request
                                 (default 0.01)

plus fixed ``watchdog.divergences == 0`` and
``serve.compiles_after_warmup == 0`` (recorded by serve_bench after its
warmup phase; absent in runs without a warmup notion → passes).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from dataclasses import dataclass

from .histogram import Histogram

# timestamped window verdicts behind the burn-rate advisory: the
# counters alone can't support a time cap (one ancient breached window
# would dominate forever), so note_window() keeps a bounded in-process
# record of (monotonic t, breached) per evaluated window
_WINDOWS_CAP = 4096
_WINDOWS_LOCK = threading.Lock()
_WINDOWS: deque = deque(maxlen=_WINDOWS_CAP)


def _reinit_lock_after_fork_in_child() -> None:
    # same idiom as obs/flight.py: a supervisor thread may hold the
    # record lock at fork time; the child is single-threaded here
    global _WINDOWS_LOCK
    _WINDOWS_LOCK = threading.Lock()


os.register_at_fork(after_in_child=_reinit_lock_after_fork_in_child)


@dataclass(frozen=True)
class SLO:
    name: str
    kind: str  # "quantile_max" | "counter_max" | "ratio_max"
    bound: float
    # quantile_max
    histogram: str | None = None
    q: float = 0.99
    # counter_max / ratio_max
    counter: str | None = None
    denominator: str | None = None


@dataclass
class SLOResult:
    name: str
    ok: bool
    observed: float | None
    bound: float
    detail: str = ""

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "ok": self.ok,
            "observed": self.observed,
            "bound": self.bound,
            "detail": self.detail,
        }


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def default_slos() -> list[SLO]:
    return [
        SLO(
            name="serve_wait_p99",
            kind="quantile_max",
            histogram="serve.wait_ms",
            q=0.99,
            bound=_env_float("ETH_SPECS_SLO_WAIT_P99_MS", 250.0),
        ),
        SLO(
            name="degraded_rate",
            kind="ratio_max",
            counter="serve.degraded_items",
            denominator="serve.requests",
            bound=_env_float("ETH_SPECS_SLO_DEGRADED_RATE", 0.01),
        ),
        SLO(name="watchdog_divergences", kind="counter_max",
            counter="watchdog.divergences", bound=0),
        SLO(name="compiles_after_warmup", kind="counter_max",
            counter="serve.compiles_after_warmup", bound=0),
    ]


def _eval_one(slo: SLO, snap: dict) -> SLOResult:
    counters = snap.get("counters", {})
    if slo.kind == "quantile_max":
        hsnap = snap.get("histograms", {}).get(slo.histogram)
        if not hsnap or not hsnap.get("count"):
            return SLOResult(slo.name, True, None, slo.bound,
                             f"no samples in {slo.histogram} (vacuous pass)")
        observed = Histogram.from_snapshot(hsnap).quantile(slo.q)
        return SLOResult(
            slo.name, observed <= slo.bound, round(observed, 3), slo.bound,
            f"p{int(slo.q * 100)}({slo.histogram}) over {hsnap['count']} samples",
        )
    if slo.kind == "counter_max":
        observed = counters.get(slo.counter, 0)
        return SLOResult(slo.name, observed <= slo.bound, observed, slo.bound,
                         slo.counter)
    if slo.kind == "ratio_max":
        num = counters.get(slo.counter, 0)
        den = counters.get(slo.denominator, 0)
        if den == 0:
            # no traffic: clean iff nothing degraded either
            return SLOResult(slo.name, num == 0, float(num), slo.bound,
                             f"{slo.counter}={num} with {slo.denominator}=0")
        observed = num / den
        return SLOResult(slo.name, observed <= slo.bound, round(observed, 6),
                         slo.bound, f"{slo.counter}/{slo.denominator}")
    return SLOResult(slo.name, False, None, slo.bound, f"unknown SLO kind {slo.kind!r}")


def evaluate(snap: dict | None = None, slos: list[SLO] | None = None) -> list[SLOResult]:
    """Evaluate ``slos`` (default: :func:`default_slos`) against ``snap``
    (default: the live registry snapshot). A breach observed against the
    LIVE registry is a flight-recorder trigger — the process just failed
    its objectives, so it leaves a postmortem bundle; evaluating a loaded
    report (snap passed in) is inspection, not an incident, and never
    dumps."""
    live = snap is None
    if snap is None:
        from .registry import get_registry

        snap = get_registry().snapshot()
    results = [_eval_one(s, snap) for s in (slos if slos is not None else default_slos())]
    if live and not passed(results):
        from . import flight

        flight.trigger_dump(
            "slo.breach",
            detail=",".join(r.name for r in results if not r.ok),
            extra={"slo": report(results)},
        )
    return results


def passed(results: list[SLOResult]) -> bool:
    return all(r.ok for r in results)


def report(results: list[SLOResult]) -> dict:
    """JSON-able summary: {ok, violations: [names], results: [...]}."""
    return {
        "ok": passed(results),
        "violations": [r.name for r in results if not r.ok],
        "results": [r.as_dict() for r in results],
    }


def note_window(breached: bool, t: float | None = None) -> None:
    """Record one evaluated supervision window's verdict: bumps the
    ``slo.windows[_breached]`` counters AND appends a timestamped record
    so :func:`burn_rate` can answer time-capped queries. The front door
    supervisor calls this once per probe window with traffic
    (frontdoor._burn_step)."""
    from .registry import get_registry

    reg = get_registry()
    reg.count("slo.windows", 1)
    if breached:
        reg.count("slo.windows_breached", 1)
    with _WINDOWS_LOCK:
        _WINDOWS.append((time.monotonic() if t is None else t, bool(breached)))


def reset_windows_for_tests() -> None:
    with _WINDOWS_LOCK:
        _WINDOWS.clear()


def burn_rate(snap: dict | None = None, window_s: float | None = None) -> dict | None:
    """Windowed burn-rate advisory: the fraction of supervision probe
    windows (with traffic) whose window-local wait p99 breached the
    objective (recorded via :func:`note_window`).

    With ``window_s=None`` this reads the cumulative ``slo.windows`` /
    ``slo.windows_breached`` counters from ``snap`` (default: live
    registry) — the whole-run advisory. With ``window_s`` set, only
    windows recorded within the last ``window_s`` seconds count, so one
    ancient breached window can't dominate the advisory forever; this
    uses the live in-process records and therefore ignores ``snap``
    (a loaded report has no timestamps to cap by).

    Returns ``{"windows", "breached", "burn_rate"}`` (plus
    ``"window_s"`` when capped) or None when no window qualifies. A p99
    SLO that only breaches at the end of a long run looks fine in the
    run-wide histogram; the burn rate says how much of the RUN was
    spent out of budget. Advisory, never gating (lower is better)."""
    if window_s is not None:
        cutoff = time.monotonic() - float(window_s)
        with _WINDOWS_LOCK:
            records = [b for (t, b) in _WINDOWS if t >= cutoff]
        if not records:
            return None
        breached = sum(1 for b in records if b)
        return {
            "windows": len(records),
            "breached": breached,
            "burn_rate": round(breached / len(records), 6),
            "window_s": float(window_s),
        }
    if snap is None:
        from .registry import get_registry

        snap = get_registry().snapshot()
    counters = snap.get("counters", {})
    windows = counters.get("slo.windows", 0)
    if not windows:
        return None
    breached = counters.get("slo.windows_breached", 0)
    return {
        "windows": int(windows),
        "breached": int(breached),
        "burn_rate": round(breached / windows, 6),
    }
