"""Dynamic micro-batcher: coalesce futures, flush on size/deadline/pressure.

Requests land in a condition-guarded deque; one batcher thread blocks in
:meth:`next_flush` until a flush condition holds:

  * **size** — ``max_batch`` requests are queued (full bucket, best
    amortization);
  * **pressure** — total admitted load (queued + in-flight, via the
    admission controller's depth) crossed the pressure threshold: under
    heavy load waiting out the deadline only grows the queue, so the
    batcher ships what it has immediately;
  * **deadline** — the OLDEST queued request has waited ``max_wait_s``:
    a lone low-load request never waits more than the latency budget
    for co-riders that aren't coming;
  * **idle** (opt-in, ``ServeConfig.idle_flush``) — the dispatch
    pipeline is empty: a single synchronous submitter (gen pool
    workers) flushes immediately instead of paying the deadline;
  * **close** — service shutdown drains the remainder.

The flush reason is first-class data (``serve.flush.<reason>``
counters): the smoke test asserts it saw both a size flush under load
and a deadline flush under trickle, which is the observable definition
of "dynamic" batching.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable

from eth_consensus_specs_tpu.analysis import lockwatch
from eth_consensus_specs_tpu.obs import waterfall


@dataclass
class Request:
    kind: str  # "bls" | "htr" | "state_root" | "agg"
    payload: tuple
    cost_bytes: int
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.monotonic)
    prepped: Any = None  # host-prep artifact (packed words etc.)
    released: bool = False  # admission slot handed back (exactly once)
    # trace context captured at submit time (obs/trace.py): carried
    # through the batcher hand-off so flush/dispatch events can link
    # this request across the submit→batch→dispatch thread boundaries
    trace: Any = None
    # waterfall stamp vector (obs/waterfall.py): monotonic marks written
    # at each pipeline boundary, folded into serve.stage_ms.* at resolve
    stamps: dict = field(default_factory=dict)
    # known-answer canary (obs/canary.py): rides the normal pipeline but
    # is exempt from admission accounting and excluded from the SLO-fed
    # serve.requests / serve.wait_ms stats — a canary must never shed
    # real traffic or move the latency objectives
    canary: bool = False


class MicroBatcher:
    def __init__(self):
        # under ETH_SPECS_ANALYSIS_LOCKWATCH the condition's INNER lock
        # is order-watched (wait() releases through the wrapper, so the
        # per-thread held stack stays truthful across waits); an RLock
        # because next_flush re-enters the condition recursively
        self._cond = threading.Condition(
            lockwatch.wrap(threading.RLock(), "serve.batcher.MicroBatcher._cond")
        )
        self._queue: deque[Request] = deque()
        self._closed = False
        # what the waiting batch thread last asked to be woken for
        # (next_flush): a queue this long, or a flush policy that looks
        # at more than the queue
        self._wake_len = 1
        self._wake_fn: Callable[[], bool] | None = None

    def put(self, req: Request) -> None:
        with self._cond:
            if self._closed:
                raise RuntimeError("service is shut down")
            self._queue.append(req)
            waterfall.mark(req.stamps, "queued")
            # the batch thread sleeps until the queue's first request and
            # then to the oldest one's deadline; only a put that can change
            # its decision wakes it. A wake-up a put was a system call in
            # every submit and a thread contending for the GIL with the
            # submitter, whose block of requests then missed the deadline
            n = len(self._queue)
            if n == 1 or n >= self._wake_len or (self._wake_fn is not None and self._wake_fn()):
                self._cond.notify_all()

    def qsize(self) -> int:
        with self._cond:
            return len(self._queue)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def next_flush(
        self,
        max_batch: int,
        max_wait_s: float,
        pressure_fn: Callable[[], bool] | None = None,
        idle_fn: Callable[[], bool] | None = None,
    ) -> tuple[list[Request], str] | None:
        """Block until a flush is due; returns (requests, reason), or
        None when the batcher is closed and drained. ``idle_fn`` (the
        opt-in single-submitter fast path) flushes immediately when the
        downstream pipeline is idle — waiting out the deadline there
        only adds latency, since co-riders accumulate naturally while a
        dispatch is in flight, not while the pipeline sits empty."""
        with self._cond:
            self._wake_len = max_batch
            # the idle policy's answer changes with no put at all: every
            # put re-asks it, as before
            self._wake_fn = (lambda: True) if idle_fn is not None else pressure_fn
            while not self._queue:
                if self._closed:
                    return None
                self._cond.wait()
            reason = None
            while reason is None:
                if self._closed:
                    reason = "close"
                elif len(self._queue) >= max_batch:
                    reason = "size"
                elif pressure_fn is not None and pressure_fn():
                    reason = "pressure"
                elif idle_fn is not None and idle_fn():
                    reason = "idle"
                else:
                    remaining = max_wait_s - (time.monotonic() - self._queue[0].t_submit)
                    if remaining <= 0:
                        reason = "deadline"
                    else:
                        self._cond.wait(timeout=remaining)
                        if not self._queue:
                            # defensive only (this thread is the sole
                            # consumer today): restart with ALL the same
                            # flush-policy callbacks
                            return None if self._closed else self.next_flush(
                                max_batch, max_wait_s, pressure_fn, idle_fn
                            )
            batch = [self._queue.popleft() for _ in range(min(len(self._queue), max_batch))]
            return batch, reason
