"""The program's own spans from the host planes of the profiler's trace, and
which of them a device-idle gap falls under. `obs.span` enters a
`jax.profiler.TraceAnnotation`, so every span of the program that ran while
the profiler did is an event of its thread's line, on the device trace's
clock. The reduction works on plain tuples, so the tests feed it a hand-built
trace."""

from __future__ import annotations

import os
import re

from benchmark import xplane

# where benchmark/run.py writes the one trace on disk
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".bench_trace")


def read_host_spans(trace_dir: str, pattern: str) -> list[list[tuple]]:
    """One list a host thread that has any: (name, start, end) in seconds of
    the events whose name matches."""
    rx = re.compile(pattern)
    threads = []
    for plane in xplane._profile(trace_dir).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows = [(e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                    for e in line.events if rx.match(e.name)]
            if rows:
                threads.append(rows)
    return threads


def innermost(rows: list[tuple]) -> list[tuple]:
    """One thread's spans as (name, start, end) pieces that do not overlap:
    at each instant the innermost span open. A span's pieces add up to its
    self time: its duration less what its child spans cover."""
    out: list[tuple] = []
    open_spans: list[tuple] = []  # (name, end), outermost first
    at = 0.0

    def close_until(t: float) -> None:
        nonlocal at
        while open_spans and open_spans[-1][1] <= t:
            name, end = open_spans.pop()
            if end > at:
                out.append((name, at, end))
                at = end

    for name, start, end in sorted(rows, key=lambda r: (r[1], -r[2])):
        close_until(start)
        if open_spans and start > at:
            out.append((open_spans[-1][0], at, start))
        at = max(at, start)
        open_spans.append((name, end))
    close_until(float("inf"))
    return out


def _overlap(a: float, b: float, intervals: list[tuple]) -> float:
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e in intervals)


def name_gaps(gaps: list[tuple], threads: list[list[tuple]], waits: set) -> list[tuple]:
    """For each (start, end) gap: (span, gap seconds, {span: self seconds over
    the gap}), the span being the one with most self time over the gap on any
    thread (`threads`: each thread's `innermost` pieces), None where no span
    of the program is open. A span in `waits` (a thread waiting for
    work) counts only where no other span is open on any thread: the batch
    thread waits all through a dispatch and would else name every gap."""
    out = []
    for a, b in gaps:
        pieces = [(name, max(s, a), min(e, b))
                  for rows in threads for name, s, e in rows if s < b and e > a]
        working = xplane.union([(s, e) for name, s, e in pieces if name not in waits])
        self_s: dict[str, float] = {}
        for name, s, e in pieces:
            seconds = e - s - (_overlap(s, e, working) if name in waits else 0.0)
            if seconds > 0:
                self_s[name] = self_s.get(name, 0.0) + seconds
        span = max(self_s, key=self_s.get) if self_s else None
        out.append((span, b - a, self_s))
    return out
