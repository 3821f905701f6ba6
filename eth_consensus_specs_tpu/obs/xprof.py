"""XLA-derived attribution: compile timing, cost/memory analyses, and a
cost-model cross-check against the hand-computed ``work_bytes``.

The roofline verdicts (obs/gates.py) judge every device timing against
``work_bytes`` the *call site* computed by hand — 96 bytes per hash,
trees × compressions, and so on. That model has never been checked
against what XLA actually compiled. This module asks the compiler:

  * :func:`analyze` AOT-lowers and compiles a jitted entry point at a
    given shape, timing ``lower()`` + ``compile()`` into the
    ``xprof.compile_ms`` (+ per-kernel) histograms;
  * from the compiled executable it pulls ``cost_analysis()`` (flops,
    bytes accessed) and ``memory_analysis()`` (argument / output / temp
    bytes) and publishes them as per-kernel gauges
    (``xprof.<kernel>.flops``, ``.bytes_accessed``, ``.arg_bytes``,
    ``.out_bytes``, ``.temp_bytes``, ``.peak_bytes``);
  * when the call site supplies its hand model (``hand_bytes``), the
    cross-check below runs.

**The cross-check is one-sided by design.** The hand model is an
*algorithmic floor* — the bytes the kernel must move if it reads each
input once and writes each output once. XLA's ``bytes accessed`` counts
the traffic the compiled program actually performs, which is ≥ the
floor and legitimately far above it on some backends (the CPU scan-form
sha256 carries its message schedule through memory every round: ~16×
the floor; the TPU unrolled form sits near 1×). So:

  * ``xprof.<kernel>.bytes_amplification`` (gauge) = XLA / hand — the
    honest statement of how much the compiled program amplifies the
    floor;
  * ``xprof.<kernel>.cost_model_rel_err`` (gauge) = (hand − XLA) / XLA —
    **positive** means the hand model claims MORE traffic than the
    compiler emitted, i.e. the roofline verdicts are being judged
    against fictional bytes; beyond ``ETH_SPECS_OBS_XPROF_TOL``
    (default 0.25) that bumps the advisory counter
    ``xprof.cost_model_mismatch`` (+ per-kernel) and emits an event.
    The CI obs-report job asserts this counter is zero on a clean run.

**What XLA compiled, where** (:func:`install_compile_listener`). The
above times compiles this module asks for. What the process compiles on
its own (a first dispatch, an eager op traced anew in every flush) JAX
reports through ``jax.monitoring``, with the function's name: the
listener files the milliseconds of each of a compile's four phases under
the innermost ``waterfall.leg`` open on the compiling thread (``none``
outside one): ``xla.trace_ms.<leg>`` (the OUTERMOST trace alone: JAX
times every nested ``jit`` inside its caller's trace),
``xla.lower_ms.<leg>``, ``xla.cache_read_ms.<leg>`` (a persistent-cache
hit: read, deserialise and load of the executable) and
``xla.compile_ms.<leg>``, every backend-compile event. JAX's
backend-compile event spans the cache's lookup, so a hit counts as one
and what XLA compiled anew is ``xla.compile_ms`` less
``xla.cache_read_ms``. Each backend compile also emits an ``xla.compile``
event (``fun_name``, ``leg``, ``ms``, ``cache_hit``, and ``trace_ms``,
``lower_ms``, ``cache_read_ms`` of the same function). A warm process
pays trace and lowering in full and the read in place of the compile.
``serve.compiles`` (serve/buckets.py) counts the FIRST SIGHTING of a
shape key, what the program believes it compiled; these count XLA's own
events.

Ambient capture is **opt-in** (``ETH_SPECS_OBS_XPROF=1``): an AOT
``lower().compile()`` does not populate the jit call cache, so ambient
analysis roughly doubles per-shape compile cost — fine for benches,
smokes, and targeted tests; wrong as a tax on the timeout-bound tier-1
suite. Everything degrades to a counted no-op
(``xprof.analysis_unavailable``) on backends/versions that don't expose
the analyses.
"""

from __future__ import annotations

import os
import threading
import time

from . import waterfall
from .registry import get_registry, obs_enabled

_SEEN_LOCK = threading.Lock()
_SEEN: set[tuple] = set()
_LISTENING = False
# JAX reports each phase of a compile on the compiling thread, in order:
# trace, lowering, and the backend compile, which on a persistent-cache hit
# spans a `cache_hits` event and the read's own duration
_COMPILING = threading.local()
_TRACE = "jaxpr_trace_duration"
_LOWER = "jaxpr_to_mlir_module_duration"
_CACHE_READ = "cache_retrieval_time_sec"
_COMPILE = "backend_compile_duration"
_PHASES = (_TRACE, _LOWER, _CACHE_READ, _COMPILE)

_DEFAULT_TOL = 0.25


def _reinit_lock_after_fork_in_child() -> None:
    # fork-safety: ambient capture can run on any serving thread; a
    # child forked mid-analysis must get a fresh, unheld lock
    global _SEEN_LOCK
    _SEEN_LOCK = threading.Lock()


os.register_at_fork(after_in_child=_reinit_lock_after_fork_in_child)


def enabled() -> bool:
    """Ambient capture gate (explicit ``analyze(..., force=True)`` calls
    ignore it)."""
    return obs_enabled() and os.environ.get("ETH_SPECS_OBS_XPROF", "0") not in (
        "0", "false", "",
    )


def tolerance() -> float:
    raw = os.environ.get("ETH_SPECS_OBS_XPROF_TOL", "")
    try:
        return float(raw) if raw else _DEFAULT_TOL
    except ValueError:
        return _DEFAULT_TOL


def reset_for_tests() -> None:
    with _SEEN_LOCK:
        _SEEN.clear()


# ------------------------------------------------------- compile listener --


class _Compiling:
    """One thread's compile in flight. JAX times every nested ``jit`` and
    every eager primitive traced inside a caller, each inside its caller's
    duration: `depth` counts the phases open on the thread, and a trace that
    closes inside another phase is left to the duration that spans it. (A
    lowering or a compile is filed wherever it closes, as every backend
    compile always was; one inside a trace, an eager op on concrete values
    run while its caller is traced, lies in that trace's milliseconds too.)
    `trace` and `lower` are (fun_name, ms) of the last phase of each kind,
    `cache_read_ms` and `cache_hit` the persistent cache's, all for the next
    compile's event."""

    __slots__ = ("depth", "trace", "lower", "cache_read_ms", "cache_hit")

    def __init__(self):
        self.depth = 0
        self.forget()

    def forget(self) -> None:
        self.trace = self.lower = ("", 0.0)
        self.cache_read_ms = 0.0
        self.cache_hit = False


def _compiling() -> _Compiling:
    st = getattr(_COMPILING, "state", None)
    if st is None:
        st = _COMPILING.state = _Compiling()
    return st


def _phase_ms(phase: tuple, fun_name: str) -> float:
    """The milliseconds of a pending phase if it was `fun_name`'s: JAX names
    the trace ``f`` and the module ``jit(f)``."""
    name, ms = phase
    return round(ms, 3) if name == fun_name or fun_name.endswith(f"({name})") else 0.0


def _on_scalar(name: str, _value, **_) -> None:
    # JAX records a phase's start as a scalar at its entry, the duration
    # at its exit (the cache's read has no entry: it is no phase of JAX's)
    if name.rsplit("/", 1)[-1] in _PHASES:
        _compiling().depth += 1


def _on_event(name: str, **_) -> None:
    if name.endswith("compilation_cache/cache_hits"):
        _compiling().cache_hit = True


def _on_duration(name: str, seconds: float, fun_name: str = "", **_) -> None:
    phase = name.rsplit("/", 1)[-1]
    if phase not in _PHASES:
        return
    st = _compiling()
    fun_name, ms = str(fun_name), float(seconds) * 1e3
    if phase != _CACHE_READ:
        st.depth = max(st.depth - 1, 0)
        if st.depth and phase == _TRACE:
            return
    leg = waterfall.current_leg() or "none"
    reg = get_registry()
    if phase == _TRACE:
        st.trace = (fun_name, ms)
        reg.observe(f"xla.trace_ms.{leg}", ms)
    elif phase == _LOWER:
        st.lower = (fun_name, ms)
        reg.observe(f"xla.lower_ms.{leg}", ms)
    elif phase == _CACHE_READ:
        st.cache_read_ms = ms  # inside the backend-compile event that follows
        reg.observe(f"xla.cache_read_ms.{leg}", ms)
    else:
        reg.observe(f"xla.compile_ms.{leg}", ms)
        reg.emit({"kind": "xla.compile", "fun_name": fun_name, "leg": leg,
                  "ms": round(ms, 3), "cache_hit": st.cache_hit,
                  "trace_ms": _phase_ms(st.trace, fun_name),
                  "lower_ms": _phase_ms(st.lower, fun_name),
                  "cache_read_ms": round(st.cache_read_ms, 3)})
        st.forget()


def install_compile_listener() -> None:
    """Once a process (the service installs it at start-up; not
    env-gated: a dict update an event, nothing with ``ETH_SPECS_OBS=0``)."""
    global _LISTENING
    with _SEEN_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    import jax.monitoring as mon

    mon.register_scalar_listener(_on_scalar)
    mon.register_event_listener(_on_event)
    mon.register_event_duration_secs_listener(_on_duration)


# --------------------------------------------------------------- analyses --


def _cost_analysis(compiled) -> dict | None:
    """Normalized ``cost_analysis()``: jax returns a list of per-program
    dicts on some versions, a plain dict on others; anything else (or a
    backend that doesn't implement it) degrades to None."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        return dict(ca) if isinstance(ca, dict) else None
    except Exception:
        return None


def _memory_analysis(compiled) -> dict | None:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        out = {
            "arg_bytes": int(ma.argument_size_in_bytes),
            "out_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        # the executable's resident working set for one execution —
        # what an OOM postmortem compares against device memory
        out["peak_bytes"] = (
            out["arg_bytes"] + out["out_bytes"] + out["temp_bytes"] + out["alias_bytes"]
        )
        return out
    except Exception:
        return None


def cross_check(kernel: str, hand_bytes: float, xla_bytes: float) -> dict:
    """Hand ``work_bytes`` floor vs XLA bytes-accessed (see module doc
    for why this is one-sided). Publishes the rel-err/amplification
    gauges; past tolerance, bumps the advisory counter + event."""
    reg = get_registry()
    rel_err = (hand_bytes - xla_bytes) / max(xla_bytes, 1.0)
    amp = xla_bytes / max(hand_bytes, 1.0)
    reg.gauge(f"xprof.{kernel}.cost_model_rel_err", round(rel_err, 6))
    reg.gauge(f"xprof.{kernel}.bytes_amplification", round(amp, 3))
    ok = rel_err <= tolerance()
    if not ok:
        reg.count("xprof.cost_model_mismatch", 1)
        reg.count(f"xprof.cost_model_mismatch.{kernel}", 1)
        reg.emit({
            "kind": "xprof.cost_model_mismatch",
            "kernel": kernel,
            "hand_bytes": float(hand_bytes),
            "xla_bytes": float(xla_bytes),
            "rel_err": round(rel_err, 6),
            "tolerance": tolerance(),
        })
    return {
        "hand_bytes": float(hand_bytes),
        "rel_err": round(rel_err, 6),
        "bytes_amplification": round(amp, 3),
        "cost_model_ok": ok,
    }


def analyze(
    kernel: str,
    jitted,
    args: tuple,
    *,
    hand_bytes: float | None = None,
    dims: tuple = (),
    force: bool = False,
) -> dict | None:
    """AOT ``jitted.lower(*args).compile()`` once per (kernel, dims):
    time the compile into ``xprof.compile_ms`` / ``.<kernel>``, publish
    the executable's cost/memory analyses as gauges, cross-check against
    ``hand_bytes`` when given. ``args`` are the lowering arguments —
    ``jax.ShapeDtypeStruct``s for array params, literal values for
    static ones. Returns the captured dict (tests assert on it), None
    when disabled or already captured; never raises."""
    if not (force or enabled()):
        return None
    key = (kernel, *map(int, dims))
    with _SEEN_LOCK:
        if key in _SEEN:
            return None
        _SEEN.add(key)
    reg = get_registry()
    try:
        t0 = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        ms = (time.perf_counter() - t0) * 1e3
    except Exception:
        reg.count("xprof.analysis_unavailable", 1)
        return None
    reg.observe("xprof.compile_ms", ms)
    reg.observe(f"xprof.compile_ms.{kernel}", ms)
    captured: dict = {"kernel": kernel, "dims": list(dims), "compile_ms": round(ms, 3)}
    cost = _cost_analysis(compiled)
    mem = _memory_analysis(compiled)
    if cost is None and mem is None:
        # backend exposes neither analysis: the timing stands, the
        # attribution degrades to a counted no-op
        reg.count("xprof.analysis_unavailable", 1)
    if cost is not None:
        flops = cost.get("flops")
        xla_bytes = cost.get("bytes accessed")
        if flops is not None:
            reg.gauge(f"xprof.{kernel}.flops", float(flops))
            captured["flops"] = float(flops)
        if xla_bytes is not None:
            reg.gauge(f"xprof.{kernel}.bytes_accessed", float(xla_bytes))
            captured["bytes_accessed"] = float(xla_bytes)
    if mem is not None:
        for field in ("arg_bytes", "out_bytes", "temp_bytes", "peak_bytes"):
            reg.gauge(f"xprof.{kernel}.{field}", mem[field])
        captured.update(mem)
    if hand_bytes and captured.get("bytes_accessed"):
        captured.update(cross_check(kernel, hand_bytes, captured["bytes_accessed"]))
    event = {"kind": "xprof.analysis"}
    event.update(
        (k, v) for k, v in captured.items() if isinstance(v, (int, float, str, bool))
    )
    event["dims"] = ",".join(map(str, dims))
    reg.emit(event)
    return captured
