"""Graceful degradation: device path -> host oracle.

`degrade(site, device_fn, host_fn)` runs the device path; when it dies
of a DEVICE-side failure (the runtime losing the device, OOM, or an
injected fault) it retries once through `retrying` — transient allocator
pressure and nth-shot injections recover here — then falls back to the
host oracle so the run completes slower rather than not at all. Logic
errors (anything that doesn't classify as a device failure) propagate:
masking a real bug behind the oracle would un-couple the device path
from the host oracle it is checked against. A COMPILE refusal is a logic error
in this sense: the compiler rejecting a kernel is not a device dying
under load, and answering from the host would hide that the device path
does not exist on this machine.
"""

from __future__ import annotations

import re

from eth_consensus_specs_tpu import obs

from .retry import retrying
from .spec import FaultInjected

# substrings of RuntimeError messages that identify device-side death.
# Deliberately NARROW (allocator failure vocabulary only): a marker like
# "device" would also match shape/transfer logic errors ("incompatible
# shapes when transferring to device") and silently mask real kernel
# bugs behind the host oracle.
_DEVICE_ERROR_MARKERS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "failed to allocate",
)
# status codes with which the XLA runtime reports that the device or its
# connection is gone (as opposed to the program being wrong)
_RUNTIME_LOST_STATUS = ("unavailable:", "aborted:", "data_loss:", "deadline_exceeded:")
# "oom" needs a word boundary: plain containment would also match
# "room"/"bloom" in unrelated error messages
_OOM_RE = re.compile(r"\boom\b")


def is_device_failure(exc: BaseException) -> bool:
    """True for failures of the accelerator runtime (safe to degrade),
    False for logic errors (must propagate)."""
    if isinstance(exc, (FaultInjected, MemoryError)):
        return True
    if getattr(exc, "degradable", False):
        # an exception type may declare itself environmental damage
        # rather than a logic error (ops/snapshot.py's torn/corrupt
        # checkpoint refusals): degrading to the host path re-derives
        # the state instead of serving a wrong answer
        return True
    if not isinstance(exc, RuntimeError):  # XlaRuntimeError is one
        return False
    msg = str(exc).lower()
    if "compil" in msg:
        # the compiler refused the kernel (no memory for it, a layout, an
        # unsupported op): the same vocabulary as an allocator failure,
        # but nothing died and a retry cannot help
        return False
    if "xla" in type(exc).__name__.lower() and msg.startswith(_RUNTIME_LOST_STATUS):
        return True
    return bool(_OOM_RE.search(msg)) or any(
        marker in msg for marker in _DEVICE_ERROR_MARKERS
    )


def degrade(site: str, device_fn, host_fn, *, attempts: int = 2):
    """Run ``device_fn()`` with `attempts` tries (retrying on device-side
    failures only), then fall back to ``host_fn()`` with a
    ``fault.degraded`` counter + event breadcrumb."""
    try:
        return retrying(
            device_fn,
            name=site,
            attempts=attempts,
            retry_on=is_device_failure,
            base_delay=0.02,
            max_delay=0.5,
        )
    except BaseException as exc:
        if not is_device_failure(exc):
            raise
        obs.count("fault.degraded", 1)
        obs.count(f"fault.degraded.{site}", 1)
        obs.event("fault.degraded", site=site, error=repr(exc)[:200])
        # black-box the moment of device death: what the process was
        # doing when the accelerator gave out (obs/flight.py; no-op
        # without ETH_SPECS_OBS_POSTMORTEM_DIR)
        obs.flight.trigger_dump(
            "fault.degrade", detail=site, extra={"error": repr(exc)[:500]}
        )
        return host_fn()
