"""In-process observability registry: spans, counters, event stream.

One process-wide thread-safe `Registry` holds

  * **counters** — monotonically increasing named totals
    (``sha256.compressions``, ``merkle.real_hashes``, ``watchdog.checks``);
  * **span aggregates** — per-name call count / total / min / max wall
    seconds with `block_until_ready` semantics (the span blocks on its
    ``result`` before stopping the clock, so async dispatch can't report
    a kernel as free), plus a roofline verdict via obs/gates.py whenever
    the span declared its ``work_bytes``;
  * **events** — a bounded in-memory ring of structured records, mirrored
    to a JSONL sink when ``ETH_SPECS_OBS_JSONL`` names a file.

Spans nest through a thread-local stack: each record carries its parent
span name and depth, so ``epoch.justification`` inside
``epoch.accounting`` is attributable in both the registry and the
Perfetto trace (the span also enters a ``jax.profiler.TraceAnnotation``
via utils/profiling.annotate, so the same names appear in
TensorBoard/Perfetto when a `utils.profiling.trace` region is live).

Everything degrades to near-zero cost: ``ETH_SPECS_OBS=0`` turns every
entry point into a no-op, and all jax interaction is lazy + best-effort
so the registry works in processes that never import jax.
"""

from __future__ import annotations

import json
import os
import threading
import time

from . import flight, gates, trace
from .histogram import Histogram

_MAX_EVENTS = 10_000

# cached once per process (refreshed in the at-fork hook): emit() stamps
# every event with its origin pid so the fleet timeline assembler
# (obs/timeline.py) can group one JSONL stream's events per process
_PID = os.getpid()


def refresh_enabled() -> bool:
    """Re-read ETH_SPECS_OBS into the cached module flag. The flag is
    resolved once at import so the hot paths don't pay an environ lookup
    per span/counter call; processes that flip the env var mid-run
    (tests) call this to apply it."""
    global _ENABLED
    _ENABLED = os.environ.get("ETH_SPECS_OBS", "1") not in ("0", "false", "")
    return _ENABLED


_ENABLED = True
refresh_enabled()


def obs_enabled() -> bool:
    return _ENABLED


class _SpanHandle:
    """Live span: assign ``.result`` to the device value the span produced
    so the exit path can block on it (dispatch-acknowledged-but-not-
    executed work then shows up as time, not as a suspiciously free op)."""

    __slots__ = ("name", "attrs", "t0", "parent", "depth", "result", "seconds",
                 "_annotation", "_registry", "_trace")

    def __init__(self, registry: "Registry", name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.result = None
        self.seconds = 0.0  # set at exit, raised body or not (waterfall.leg reads it)
        self._registry = registry
        self._annotation = None

    def __enter__(self):
        stack = self._registry._span_stack()
        self.parent = stack[-1] if stack else None
        self.depth = len(stack)
        stack.append(self.name)
        # under an active trace context (obs/trace.py) the span becomes a
        # trace span: its event carries trace_id/span_id/parent_span so
        # it stitches across thread and process boundaries
        self._trace = trace.enter_span()
        self._annotation = _enter_annotation(self.name)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.result is not None:
            _block_until_ready(self.result)
        seconds = self.seconds = time.perf_counter() - self.t0
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        trace.exit_span(self._trace)
        stack = self._registry._span_stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        if exc_type is None:
            self._registry.record_span(
                self.name, seconds, self.attrs, parent=self.parent, depth=self.depth,
                trace_ctx=self._trace,
            )
        return False


class _NullSpan:
    """Disabled-mode span: context manager with a writable ``result``.
    One instance per call — a shared singleton would pin the last
    assigned ``result`` (possibly a large device array) for the process
    lifetime and race across threads."""

    __slots__ = ("result",)
    seconds = 0.0  # as a live span's after its exit: a caller may read it either way

    def __init__(self):
        self.result = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.result = None
        return False


def _enter_annotation(name: str):
    """Layer the span onto the jax profiler (utils/profiling.annotate) so
    the same names show up in Perfetto/TensorBoard. Best-effort: no jax,
    no annotation — the registry side still records."""
    try:
        from eth_consensus_specs_tpu.utils.profiling import annotate

        ann = annotate(name)
        ann.__enter__()
        return ann
    except Exception:
        return None


def _block_until_ready(x):
    try:
        import jax

        jax.block_until_ready(x)
    except Exception:
        pass


class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, dict] = {}
        self.histograms: dict[str, Histogram] = {}
        self.spans: dict[str, dict] = {}
        self.events: list[dict] = []
        self._jsonl_path: str | None = os.environ.get("ETH_SPECS_OBS_JSONL") or None
        self._jsonl_fh = None

    # ------------------------------------------------------------- spans --

    def _span_stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs) -> _SpanHandle | _NullSpan:
        if not obs_enabled():
            return _NullSpan()
        return _SpanHandle(self, name, attrs)

    def record_span(
        self, name: str, seconds: float, attrs: dict | None = None,
        parent: str | None = None, depth: int = 0,
        trace_ctx=None,
    ) -> None:
        attrs = attrs or {}
        verdict = None
        work_bytes = attrs.get("work_bytes")
        if work_bytes and seconds > 0:
            # every device timing carries its roofline verdict (the
            # bench-grade gate, one implementation: obs/gates.py)
            verdict = gates.roofline_verdict(work_bytes, seconds)
        with self._lock:
            agg = self.spans.get(name)
            if agg is None:
                agg = self.spans[name] = {
                    "count": 0,
                    "total_s": 0.0,
                    "min_s": float("inf"),
                    "max_s": 0.0,
                    "work_bytes": 0,
                    "roofline_violations": 0,
                    "parent": parent,
                    "depth": depth,
                }
            agg["count"] += 1
            agg["total_s"] += seconds
            agg["min_s"] = min(agg["min_s"], seconds)
            agg["max_s"] = max(agg["max_s"], seconds)
            if work_bytes:
                agg["work_bytes"] += int(work_bytes)
            if verdict is not None:
                agg["implied_gbps"] = verdict["implied_gbps"]  # last call's rate
                if not verdict["roofline_ok"]:
                    agg["roofline_violations"] += 1
                # the aggregate verdict is the ALL-calls conjunction — one
                # impossible timing taints the span, whatever came after
                agg["roofline_ok"] = agg["roofline_violations"] == 0
        event = {"kind": "span", "name": name, "s": round(seconds, 9), "depth": depth}
        if parent:
            event["parent"] = parent
        event.update(trace.event_fields(trace_ctx))
        for k, v in attrs.items():
            # reserved event fields can't be shadowed by span attributes
            if k not in event and isinstance(v, (int, float, str, bool)):
                event[k] = v
        if verdict is not None:
            event.update(verdict)
        self.emit(event)

    # ---------------------------------------------------------- counters --

    def count(self, name: str, n: int | float = 1) -> None:
        if not obs_enabled():
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n
        # flight-recorder tap (obs/flight.py): mega-bumps above the floor
        # land in the postmortem ring; ETH_SPECS_OBS=0 never reaches here
        flight.note_count(name, n)

    def bytes_moved(self, name: str, nbytes: int) -> None:
        self.count(f"{name}.bytes_moved", int(nbytes))

    def gauge(self, name: str, value: int | float) -> None:
        """Record a point-in-time level (queue depth, in-flight bytes):
        unlike a counter it can go down — the snapshot keeps the last and
        the max, which is what capacity questions ("did the queue ever
        hit the cap?") actually need."""
        if not obs_enabled():
            return
        with self._lock:
            g = self.gauges.get(name)
            if g is None:
                g = self.gauges[name] = {"last": 0.0, "max": 0.0}
            g["last"] = value
            g["max"] = max(g["max"], value)

    # -------------------------------------------------------- histograms --

    def observe(self, name: str, value: float) -> None:
        """Record a sample into the named mergeable log-bucket histogram
        (auto-created with the shared default layout, so same-named
        histograms from any process always merge). The record path takes
        only the histogram's own O(1) lock — never the registry lock."""
        if not obs_enabled():
            return
        h = self.histograms.get(name)
        if h is None:
            with self._lock:
                h = self.histograms.setdefault(name, Histogram())
        h.record(value)

    def histogram(self, name: str) -> Histogram | None:
        return self.histograms.get(name)

    def merge_histogram(self, name: str, snap: dict) -> None:
        """Fold a serialized histogram delta (Histogram.delta_since) from
        another process into this registry's same-named histogram."""
        if not obs_enabled():
            return
        h = self.histograms.get(name)
        if h is None:
            with self._lock:
                h = self.histograms.setdefault(
                    name, Histogram(lo=snap["lo"], growth=snap["growth"])
                )
        h.merge(snap)

    def merge_gauge(self, name: str, g: dict) -> None:
        """Fold another process's gauge state in: ``last`` is latest-wins
        (the shipper is the fresher observation), ``max`` is monotonic."""
        if not obs_enabled():
            return
        with self._lock:
            cur = self.gauges.setdefault(name, {"last": 0.0, "max": 0.0})
            cur["last"] = g.get("last", cur["last"])
            cur["max"] = max(cur["max"], g.get("max", 0.0))

    def merge_span(self, name: str, agg: dict) -> None:
        """Fold another process's span-aggregate DELTA in (obs/delta.py
        ships count/total_s/work_bytes/roofline_violations as
        differences; min_s/max_s as current values — they only tighten,
        so repeated merging is idempotent). The merged roofline verdict
        stays the all-calls conjunction: one replica's impossible timing
        taints the fleet-wide span."""
        if not obs_enabled():
            return
        with self._lock:
            cur = self.spans.get(name)
            if cur is None:
                cur = self.spans[name] = {
                    "count": 0,
                    "total_s": 0.0,
                    "min_s": float("inf"),
                    "max_s": 0.0,
                    "work_bytes": 0,
                    "roofline_violations": 0,
                    "parent": agg.get("parent"),
                    "depth": agg.get("depth", 0),
                }
            cur["count"] += agg.get("count", 0)
            cur["total_s"] += agg.get("total_s", 0.0)
            cur["min_s"] = min(cur["min_s"], agg.get("min_s", float("inf")))
            cur["max_s"] = max(cur["max_s"], agg.get("max_s", 0.0))
            cur["work_bytes"] += int(agg.get("work_bytes", 0))
            cur["roofline_violations"] += agg.get("roofline_violations", 0)
            if "implied_gbps" in agg:
                cur["implied_gbps"] = agg["implied_gbps"]  # shipper's last rate
            if "roofline_ok" in agg or "roofline_ok" in cur:
                cur["roofline_ok"] = cur["roofline_violations"] == 0

    # ------------------------------------------------------------ events --

    def emit(self, event: dict) -> None:
        if not obs_enabled():
            return
        # paired clock stamps + process/thread identity on every event:
        # the fleet timeline assembler (obs/timeline.py) estimates
        # per-process clock offsets from the wall/monotonic PAIR and
        # needs pid/tid for truthful process/thread tracks. Four scalar
        # stores — the no-context fast path stays allocation-light.
        if "t_mono" not in event:
            event["t_mono"] = time.perf_counter()
            event["t_wall"] = time.time()
            event["pid"] = _PID
            event["tid"] = threading.get_ident()
        # every emitted event is also a flight-recorder entry: the ring
        # holds the last N of these when a postmortem trigger fires
        flight.note_event(event)
        with self._lock:
            self.events.append(event)
            if len(self.events) > _MAX_EVENTS:
                del self.events[: len(self.events) // 2]
            fh = self._jsonl_handle()
            # write under the lock: lines never interleave, and a
            # concurrent configure_jsonl close can't yank the handle
            # mid-write (a closed file raises ValueError, not OSError)
            if fh is not None:
                try:
                    fh.write(json.dumps(event, sort_keys=True) + "\n")
                    fh.flush()
                except (OSError, ValueError):
                    pass

    def _jsonl_handle(self):
        if self._jsonl_path is None:
            return None
        if self._jsonl_fh is None:
            try:
                self._jsonl_fh = open(self._jsonl_path, "a")
            except OSError:
                self._jsonl_path = None
        return self._jsonl_fh

    def configure_jsonl(self, path: str | None) -> None:
        with self._lock:
            if self._jsonl_fh is not None:
                try:
                    self._jsonl_fh.close()
                except OSError:
                    pass
            self._jsonl_fh = None
            self._jsonl_path = path

    # ----------------------------------------------------------- reports --

    def snapshot(self) -> dict:
        """Point-in-time copy: {counters, spans, watchdog} — the watchdog
        section is derived from its counters so one code path feeds the
        pytest report, bench, and ad-hoc inspection."""
        with self._lock:
            counters = dict(self.counters)
            gauges = {name: dict(g) for name, g in self.gauges.items()}
            hist_refs = dict(self.histograms)
            spans = {
                name: {k: (round(v, 9) if isinstance(v, float) else v) for k, v in agg.items()}
                for name, agg in self.spans.items()
            }
        kernels: dict[str, dict] = {}
        for key, val in counters.items():
            if not key.startswith("watchdog."):
                continue
            parts = key.split(".")
            if len(parts) == 3:  # watchdog.<kernel>.<checks|divergences>
                kernels.setdefault(parts[1], {})[parts[2]] = val
        return {
            "counters": counters,
            "gauges": gauges,
            # each histogram serializes under its own lock (post-snapshot
            # records may slip in — a snapshot is a point-in-time-ish view)
            "histograms": {name: h.snapshot() for name, h in hist_refs.items()},
            "spans": spans,
            "watchdog": {
                "checks": counters.get("watchdog.checks", 0),
                "divergences": counters.get("watchdog.divergences", 0),
                "kernels": kernels,
            },
        }

    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()
            self.spans.clear()
            self.events.clear()


_REGISTRY = Registry()


def get_registry() -> Registry:
    return _REGISTRY


def _reinit_locks_after_fork_in_child() -> None:
    """Fork-safety: the parent may fork (gen pool workers) while one of
    its BACKGROUND threads — the front-door supervisor merging replica
    deltas, a dispatcher bumping counters — holds an obs-layer lock.
    The child inherits that lock HELD by a thread that doesn't exist
    there, and its first obs call deadlocks forever. The child is
    single-threaded at this moment, so unconditionally re-creating
    every lock is safe; torn metric values are bounded (single-key dict
    writes) and the worker's delta baseline swallows them at init. The
    inherited JSONL handle is dropped too — its buffer may hold half a
    line another thread was writing; the child reopens lazily in append
    mode."""
    global _PID
    reg = _REGISTRY
    reg._lock = threading.Lock()
    reg._local = threading.local()
    reg._jsonl_fh = None
    _PID = os.getpid()  # the child's events must carry ITS pid
    for h in list(reg.histograms.values()):
        h._lock = threading.Lock()


os.register_at_fork(after_in_child=_reinit_locks_after_fork_in_child)
