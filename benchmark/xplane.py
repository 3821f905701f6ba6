"""From a profiler trace to intervals: which operations ran on the device,
when, and what the client's threads were doing meanwhile. The reduction to
metrics (busy union, time of the programs matching a pattern, the longest
idle gaps) works on plain tuples, so the tests feed it a hand-built trace."""

from __future__ import annotations

import glob
import itertools
import json
import os
import re
from dataclasses import dataclass, field

# lines of a TPU device plane: one event an executed program, one an HLO op
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
CLIENT_PREFIX = "client."
# a limb program is half a million operation events an execution, and each
# costs ~20 us to read from Python: the table of operations is taken from the
# first so many, the busy time from the programs' line, which is all read
OP_EVENTS_READ = 400_000


@dataclass
class Trace:
    """Times in seconds on the trace's own clock. `ops` and `modules` hold
    one list a device: (name, start, duration); `ops` may stop early."""

    ops: list[list[tuple]] = field(default_factory=list)
    modules: list[list[tuple]] = field(default_factory=list)
    client: list[tuple] = field(default_factory=list)
    window_s: float = 0.0
    requests: int = 0

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with open(path) as f:
            raw = json.load(f)
        as_tuples = lambda rows: [tuple(r) for r in rows]  # noqa: E731
        return cls(
            ops=[as_tuples(d) for d in raw["ops"]],
            modules=[as_tuples(d) for d in raw["modules"]],
            client=as_tuples(raw["client"]),
            window_s=raw["window_s"],
            requests=raw["requests"],
        )


def add_device(trace: Trace, ops: list[tuple], modules: list[tuple]) -> None:
    """A device plane counts as a device only if something ran there: a v5e's
    trace carries a second, empty `/device:` plane, and averaging over it
    would halve the busy time."""
    if ops or modules:
        trace.ops.append(ops)
        trace.modules.append(modules)


def _profile(trace_dir: str):
    """The newest .xplane.pb under `trace_dir`, read with JAX alone."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def read_xplane(trace_dir: str, window_s: float, requests: int) -> Trace:
    trace = Trace(window_s=window_s, requests=requests)
    for plane in _profile(trace_dir).planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            add_device(trace, *(
                [(e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                 for e in itertools.islice(lines[name].events, most)]
                if name in lines else []
                for name, most in ((OP_LINE, OP_EVENTS_READ), (MODULE_LINE, None))
            ))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.client += [
                    (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in line.events if e.name.startswith(CLIENT_PREFIX)
                ]
    return trace


def short(name: str) -> str:
    """`%fusion.407` of an operation's full HLO text."""
    return name.split(" = ", 1)[0][:80]


def describe_xplane(trace_dir: str) -> list[dict]:
    """Planes, lines and each line's first events: what to look at by hand
    before trusting a pattern."""
    out = []
    for plane in _profile(trace_dir).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({
                "plane": plane.name, "line": line.name, "events": len(events),
                "first": [[e.name, e.start_ns, e.duration_ns] for e in events[:5]],
            })
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def device_intervals(trace: Trace, device: int) -> list[tuple[float, float]]:
    """Where a program ran on one device. Its operations tile a program (the
    union of the operation events is the programs' time to 0.2 % in both of
    PR 24's cells), so the line of programs stands for them; the operations'
    own intervals where a trace has no such line."""
    rows = trace.modules[device] or trace.ops[device]
    return union([(s, s + d) for _, s, d in rows])


def busy_seconds(trace: Trace) -> float | None:
    """Seconds in which an operation ran, averaged over the devices traced."""
    if not trace.modules:
        return None
    per_device = [sum(b - a for a, b in device_intervals(trace, i)) for i in range(len(trace.ops))]
    return sum(per_device) / len(per_device)


class TraceError(Exception):
    """The trace holds something a reader cannot tell apart."""


def program_seconds(trace: Trace, pattern: str) -> tuple[float, int]:
    """(seconds, executions) of the programs whose name matches, on the
    fullest device. A program's name in the trace is `jit_<function>(<its
    fingerprint>)`, and more than one function of the program under test is
    called `run`: where more than one DISTINCT program matches, the pattern
    no longer names one kernel and the sum would be of several, so that is an
    error and never a number."""
    rx = re.compile(pattern)
    best = (0.0, 0)
    for rows in trace.modules:
        hit = [(name, d) for name, _, d in rows if rx.search(name)]
        distinct = sorted({name for name, _ in hit})
        if len(distinct) > 1:
            raise TraceError(f"{pattern!r} matches {len(distinct)} distinct programs: {distinct}")
        best = max(best, (sum(d for _, d in hit), len(hit)))
    return best


def top_device_ops(trace: Trace, most: int = 10) -> list[list]:
    """The operations that took most device time, summed by name over the
    first device's first OP_EVENTS_READ operation events; program executions
    where there is no line of operations."""
    if not trace.ops:
        return []
    totals: dict[str, float] = {}
    for name, _, d in trace.ops[0] or trace.modules[0]:
        name = short(name)
        totals[name] = totals.get(name, 0.0) + d
    return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:most]]


def idle_gaps(trace: Trace, most: int = 10) -> list[list]:
    """The longest gaps in which the first device ran nothing, each named by
    the client span that covers most of it (or `client.between_requests`)."""
    if not trace.modules:
        return []
    busy = device_intervals(trace, 0)
    gaps = [(b1, a2) for (_, b1), (a2, _) in zip(busy, busy[1:]) if a2 > b1]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:most]:
        cover: dict[str, float] = {}
        for name, s, d in trace.client:
            overlap = min(b, s + d) - max(a, s)
            if overlap > 0:
                cover[name] = cover.get(name, 0.0) + overlap
        name = max(cover, key=cover.get) if cover else CLIENT_PREFIX + "between_requests"
        out.append([name, b - a])
    return out
