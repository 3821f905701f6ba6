"""Trace context: request-scoped ids that survive threads and processes.

Spans (obs/registry.py) nest through a thread-local stack, which dies
at every thread hand-off — exactly where the serving pipeline lives
(submit thread → batch thread → dispatch thread) and where the gen pool
lives (parent process → worker process). This module carries a small
explicit context across those seams:

  * ``TraceContext(trace_id, span_id, parent_id)`` — W3C-traceparent-
    shaped ids (128-bit trace, 64-bit span, hex);
  * a thread-local **context stack**: ``activate(ctx)`` installs a
    context for a ``with`` block, ``current()`` reads it;
  * every obs span that runs under an active context becomes a trace
    span automatically: the registry asks this module for a child
    context on span entry, and the span's JSONL event carries
    ``trace_id`` / ``span_id`` / ``parent_span`` — so Perfetto (or any
    JSONL consumer) can stitch one request's spans across threads and
    processes into a single tree;
  * ``to_wire`` / ``from_wire`` — the one-string form that rides in
    queue payloads (serve Request objects, gen-pool task tuples);
  * **flow ids**: a batched dispatch span cannot *belong* to the N
    requests it serves, so it *links* them instead — the flush/dispatch
    events list each member request's wire id under ``flows`` (the
    Perfetto flow-event idiom: one producer slice, many consumer
    slices, connected by id).

Everything here is pure stdlib and allocation-light; with no active
context the per-span overhead is one thread-local read.
"""

from __future__ import annotations

import os
import random
import threading
from dataclasses import dataclass

_local = threading.local()


@dataclass(frozen=True)
class TraceContext:
    trace_id: str  # 32 hex chars (128-bit)
    span_id: str  # 16 hex chars (64-bit)
    parent_id: str | None = None  # the parent span's span_id


# ids name spans, they guard nothing: a generator seeded from the OS once
# (and again in a forked child, as `random` does for its own) and no
# system call an id. `os.urandom` here released the GIL twice in every
# submit, to whichever service thread was waiting for it.
_ids = random.Random()
os.register_at_fork(after_in_child=_ids.seed)


def _new_id(nbytes: int) -> str:
    return f"{_ids.getrandbits(8 * nbytes):0{2 * nbytes}x}"


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def current() -> TraceContext | None:
    stack = _stack()
    return stack[-1] if stack else None


def new_trace() -> TraceContext:
    """Fresh root context (new trace_id, no parent)."""
    return TraceContext(trace_id=_new_id(16), span_id=_new_id(8))


def child(ctx: TraceContext | None = None) -> TraceContext:
    """Child of ``ctx`` (default: the active context); a fresh root when
    there is nothing to be a child of."""
    if ctx is None:
        ctx = current()
    if ctx is None:
        return new_trace()
    return TraceContext(trace_id=ctx.trace_id, span_id=_new_id(8), parent_id=ctx.span_id)


class activate:
    """``with trace.activate(ctx):`` — install ``ctx`` as the thread's
    current context for the block. Re-entrant and exception-safe (plain
    stack discipline)."""

    __slots__ = ("ctx",)

    def __init__(self, ctx: TraceContext | None):
        self.ctx = ctx

    def __enter__(self) -> TraceContext | None:
        if self.ctx is not None:
            _stack().append(self.ctx)
        return self.ctx

    def __exit__(self, *exc) -> bool:
        if self.ctx is not None:
            stack = _stack()
            if stack and stack[-1] is self.ctx:
                stack.pop()
        return False


# ------------------------------------------------------- span integration --


def enter_span() -> TraceContext | None:
    """Called by the registry on span entry: under an active context the
    span becomes a trace span (child context pushed, returned); with no
    active context it returns None and costs one thread-local read."""
    cur = current()
    if cur is None:
        return None
    ctx = TraceContext(trace_id=cur.trace_id, span_id=_new_id(8), parent_id=cur.span_id)
    _stack().append(ctx)
    return ctx


def exit_span(ctx: TraceContext | None) -> None:
    if ctx is None:
        return
    stack = _stack()
    if stack and stack[-1] is ctx:
        stack.pop()


def event_fields(ctx: TraceContext | None) -> dict:
    """The JSONL event fields for a context (empty dict when None)."""
    if ctx is None:
        return {}
    fields = {"trace_id": ctx.trace_id, "span_id": ctx.span_id}
    if ctx.parent_id:
        fields["parent_span"] = ctx.parent_id
    return fields


# ------------------------------------------------------------------- wire --


def to_wire(ctx: TraceContext | None) -> str | None:
    """``trace_id-span_id`` — the form that rides in queue payloads and
    flow-link lists. The receiving side treats the wire span as the
    PARENT of whatever it runs (from_wire restores it as current)."""
    if ctx is None:
        return None
    return f"{ctx.trace_id}-{ctx.span_id}"


def from_wire(wire: str | None) -> TraceContext | None:
    if not wire:
        return None
    trace_id, _, span_id = wire.partition("-")
    if not trace_id or not span_id:
        return None
    return TraceContext(trace_id=trace_id, span_id=span_id)
