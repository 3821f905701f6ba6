"""obs — kernel-level observability: spans, op counters, divergence watchdog.

The reference pyspec has no tracing at all (SURVEY §5). This package
makes timing, counting and device-vs-host checking ambient:

  * ``obs.span("epoch.justification", work_bytes=...)`` — nested timed
    regions with block_until_ready semantics, mirrored into the jax
    profiler (Perfetto/TensorBoard) via utils/profiling.annotate, with a
    roofline verdict attached to every timing that declares its traffic;
  * ``obs.count("sha256.compressions", n)`` / ``obs.bytes_moved(...)``
    — thread-safe process counters the hot paths report into;
  * ``obs.gates`` — the roofline/digest gate logic as the single
    shared implementation;
  * ``obs.watchdog`` — always-on sampled device-vs-host recompute of
    result slices, recording match/mismatch as first-class metrics;
  * a JSONL event sink (``ETH_SPECS_OBS_JSONL=<path>``) and a pytest
    plugin (test_infra/obs_plugin.py) that emits ``obs_report.json``.

Export/attribution layer on top (this PR's tentpole):

  * ``obs.observe("serve.wait_ms", ms)`` — mergeable fixed-log-bucket
    histograms (obs/histogram.py): run-level quantiles from buckets,
    cross-process merge (gen-pool workers ship bucket deltas);
  * ``obs.trace`` — trace contexts that survive thread hand-offs and
    process boundaries; spans under an active context carry
    trace_id/span_id/parent_span in their events;
  * ``obs.export`` — Prometheus text exposition of the full snapshot
    (textfile and/or stdlib HTTP ``/metrics``); ``validate_text``
    rejects families absent from the central metric catalog
    (``obs/catalog.py`` — every counter/gauge/histogram/span name is
    declared there once, enforced by the ``obs-discipline`` speclint
    rule, docs/analysis.md);
  * ``obs.slo`` — declarative SLOs evaluated from any snapshot.

Postmortem/attribution layer (obs/flight.py + obs/xprof.py):

  * ``obs.flight`` — an always-on bounded ring of recent structured
    events (every emitted event + counter mega-bumps), dumped as a
    postmortem bundle (ring + registry + env + platform) to
    ``ETH_SPECS_OBS_POSTMORTEM_DIR`` on trigger: watchdog divergence,
    ``fault.degrade`` fallback, live SLO breach, lost gen-pool worker
    (workers ship their rings to the parent incrementally, so a
    SIGKILLed worker still leaves a black box), pytest failure, or the
    explicit ``flight.dump()`` API. ``scripts/postmortem.py`` inspects
    and diffs bundles.
  * ``obs.xprof`` — XLA-derived attribution: AOT compile timing into
    ``xprof.compile_ms`` histograms, ``cost_analysis``/
    ``memory_analysis`` published as per-kernel gauges, and a
    cross-check of the hand ``work_bytes`` floor against the
    compiler's bytes-accessed (advisory
    ``xprof.cost_model_mismatch`` counter past tolerance).

Waterfall layer (obs/waterfall.py + obs/ledger.py):

  * ``obs.waterfall`` — the request stage clock: every serve Request
    carries a monotonic stamp vector; resolve folds it into contiguous
    ``serve.stage_ms.<stage>`` histograms (unattributed time is a
    first-class ``other`` stage) and a bounded trace-id stash carries
    durations across the replica wire, so the front door attributes
    fleet-wide p99 by stage (docs/observability.md);
    ``waterfall.leg`` splits the device stage into named legs
    (``serve.stage_ms.device.<leg>``).
  * ``obs.ledger`` — the HBM residency ledger: long-lived device
    buffers register bytes per owner (``hbm.resident_bytes.<owner>``
    gauges, high-water via gauge max), embedded in every postmortem
    bundle as ``bundle["hbm"]``.

Environment:
    ETH_SPECS_OBS=0              disable all recording
    ETH_SPECS_OBS_JSONL=<path>   stream structured events as JSON lines
    ETH_SPECS_OBS_WATCHDOG=<r>   watchdog sampling rate (default 0.05;
                                 0 disables, 1 checks every call)
    ETH_SPECS_OBS_REPORT=<path>  pytest run-level report destination
    ETH_SPECS_OBS_PROM=<path>    Prometheus textfile destination
    ETH_SPECS_OBS_HTTP_PORT=<p>  serve GET /metrics on 127.0.0.1:<p>
    ETH_SPECS_OBS_POSTMORTEM_DIR=<dir>  flight-recorder bundle dir
                                 (unset: postmortem dumps are no-ops)
    ETH_SPECS_OBS_FLIGHT=<n>     flight ring capacity (default 512; 0 off)
    ETH_SPECS_OBS_FLIGHT_COUNTER_FLOOR=<n>  counter increment that rates
                                 a ring entry (default 65536)
    ETH_SPECS_OBS_XPROF=1        enable ambient XLA attribution capture
    ETH_SPECS_OBS_XPROF_TOL=<f>  cost-model mismatch tolerance (0.25)
    ETH_SPECS_SLO_WAIT_P99_MS    serve wait p99 SLO bound (default 250)
    ETH_SPECS_SLO_DEGRADED_RATE  degraded-per-request SLO bound (0.01)
"""

from __future__ import annotations

from . import (  # noqa: F401  (public submodules)
    export,
    flight,
    gates,
    ledger,
    slo,
    trace,
    waterfall,
    watchdog,
    xprof,
)
from .histogram import Histogram  # noqa: F401
from .registry import Registry, get_registry, obs_enabled  # noqa: F401


def span(name: str, **attrs):
    """Timed, nestable region. Assign ``.result`` inside the block to make
    the span block on device completion before the clock stops:

        with obs.span("merkle.subtree", work_bytes=wb) as sp:
            sp.result = kernel(x)
    """
    return get_registry().span(name, **attrs)


def count(name: str, n: int | float = 1) -> None:
    """Bump a named process counter (thread-safe, monotonic)."""
    get_registry().count(name, n)


def bytes_moved(name: str, nbytes: int) -> None:
    """Record device traffic attributed to `name` (``<name>.bytes_moved``)."""
    get_registry().bytes_moved(name, nbytes)


def gauge(name: str, value: int | float) -> None:
    """Record a point-in-time level (can go down, unlike a counter); the
    snapshot keeps last + max per gauge."""
    get_registry().gauge(name, value)


def observe(name: str, value: float) -> None:
    """Record a sample into the named mergeable log-bucket histogram
    (obs/histogram.py): O(1), lock-cheap, quantiles from buckets —
    the primitive behind run-level latency p50/p99."""
    get_registry().observe(name, value)


def histogram(name: str) -> Histogram | None:
    """The named registry histogram, or None if nothing observed yet."""
    return get_registry().histogram(name)


def event(kind: str, **fields) -> None:
    """Emit a structured event to the in-memory ring + JSONL sink."""
    get_registry().emit({"kind": kind, **fields})


def snapshot() -> dict:
    """{counters, spans, watchdog} view of the process registry."""
    return get_registry().snapshot()


def tracing(x) -> bool:
    """True when `x` is a jax tracer — instrumentation sites inside
    traceable functions use this to skip wall-clock recording at trace
    time (a trace is compiled once; counting it as an execution lies)."""
    try:
        import jax

        return isinstance(x, jax.core.Tracer)
    except Exception:
        # probe unavailable (no jax, or the jax.core alias removed): fall
        # back to the MRO. This must still CATCH tracers — misclassifying
        # a concrete array merely skips one timing, but missing a tracer
        # records a compile as an execution, the exact lie this guard
        # exists to prevent.
        return any("Tracer" in c.__name__ for c in type(x).__mro__)
