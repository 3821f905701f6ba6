"""Device-time capture: measured execution seconds per kernel dispatch,
with roofline verdicts computed from MEASURED time.

obs/xprof.py times *compiles* and audits the hand byte model against
what XLA emitted; nothing in the repo times actual device execution.
Every "fast as the hardware allows" roofline verdict so far judged a
host-side wall-clock span — batching slop, Python overhead, and sync
latency all billed to the device. This module closes that gap:

  * :func:`measure` — a context manager the dispatch seams
    (serve/service.py ``_execute``) wrap around one device dispatch
    *including its ``block_until_ready``/host-sync*, recording the
    delta into ``device.exec_ms`` + ``device.exec_ms.<kernel>``
    histograms. When the seam declares ``work_bytes`` (the same hand
    model the spans use), the measured seconds feed
    :func:`..gates.roofline_verdict` — an implied GB/s above the
    accelerator roofline bumps ``device.roofline_violations``
    (+ per-kernel) and emits an event; the CI obs-report discipline
    treats violations as a measurement bug, not a fast kernel.

:func:`measure` is a cheap ``perf_counter`` pair, active whenever obs
is on, because the serve_bench waterfall section gates on
``device.exec_ms`` being populated on every platform including CPU CI.
With ``ETH_SPECS_OBS=0`` nothing records. Never raises. A profiler
trace is the caller's to take (``utils/profiling.trace``, or the
benchmark's ``--trace 1``): every ``obs.span`` and ``waterfall.leg``
is on its clock.
"""

from __future__ import annotations

import os
import threading
import time

from . import gates
from .registry import get_registry, obs_enabled

_SEEN_LOCK = threading.Lock()
_SEEN: set[str] = set()


def _reinit_lock_after_fork_in_child() -> None:
    # a serving thread can be inside measure() at fork time; the child
    # must get a fresh, unheld lock (same idiom as xprof/flight)
    global _SEEN_LOCK
    _SEEN_LOCK = threading.Lock()


os.register_at_fork(after_in_child=_reinit_lock_after_fork_in_child)


def reset_for_tests() -> None:
    with _SEEN_LOCK:
        _SEEN.clear()


# ----------------------------------------------------------------- measure --


def record(kernel: str, seconds: float, work_bytes: float | None = None) -> dict | None:
    """Record one measured device execution. Returns the roofline
    verdict dict when ``work_bytes`` was declared, else None."""
    if not obs_enabled() or seconds < 0:
        return None
    reg = get_registry()
    ms = seconds * 1e3
    reg.observe("device.exec_ms", ms)
    reg.observe(f"device.exec_ms.{kernel}", ms)
    verdict = None
    if work_bytes:
        verdict = gates.roofline_verdict(work_bytes, max(seconds, 1e-9))
        if not verdict["roofline_ok"]:
            # measured time says the kernel beat the memory system's
            # physics: the byte model (or the sync point) is lying
            reg.count("device.roofline_violations", 1)
            reg.count(f"device.roofline_violations.{kernel}", 1)
            reg.emit({
                "kind": "device.roofline_violation",
                "kernel": kernel,
                "s": round(seconds, 9),
                "work_bytes": float(work_bytes),
                "implied_gbps": verdict["implied_gbps"],
            })
    with _SEEN_LOCK:
        first = kernel not in _SEEN
        if first:
            _SEEN.add(kernel)
    if first:
        event = {"kind": "device.exec", "kernel": kernel, "s": round(seconds, 9)}
        if verdict:
            event["implied_gbps"] = verdict["implied_gbps"]
            event["roofline_ok"] = verdict["roofline_ok"]
        reg.emit(event)
    return verdict


class _Measure:
    __slots__ = ("kernel", "work_bytes", "verdict", "_t0")

    def __init__(self, kernel: str, work_bytes: float | None):
        self.kernel = kernel
        self.work_bytes = work_bytes
        self.verdict = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            try:
                self.verdict = record(
                    self.kernel, time.perf_counter() - self._t0, self.work_bytes
                )
            except Exception:  # noqa: BLE001 — measurement must not kill a dispatch
                pass
        return False


def measure(kernel: str, work_bytes: float | None = None) -> _Measure:
    """Time one device dispatch (the ``with`` body MUST include the
    sync — ``block_until_ready`` or a host transfer — or the measured
    delta is launch latency, not execution). A body that raises records
    nothing: a degraded dispatch's timing would poison the histogram."""
    return _Measure(kernel, work_bytes)
