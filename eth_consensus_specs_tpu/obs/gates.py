"""Throughput gates — roofline verdicts + result digests, ONE implementation.

A platform that acknowledges work before executing it once produced
878 Ghash/s (~84 TB/s of implied HBM traffic); the gate that refuses
such a timing lives here, consumed by

  * obs/registry.py    — attaches a roofline verdict to every timed span
                         that declares its ``work_bytes``;
  * obs/watchdog.py    — digests device-vs-host slices;
  * gen/dumper.py      — fingerprints emitted vector parts so the
                         cross-generator byte-diff can compare runs from
                         the observability stream alone;
  * tests              — assert the verdict/digest semantics directly.

The benchmark's own peaks are in ``benchmark/peaks.py``.
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

import numpy as np

# Peak memory bandwidth of ONE chip in bytes/s, keyed by the
# ``device_kind`` JAX reports, each row with its source. A device that is
# not in the table is an error, never a default: a ceiling borrowed from
# another chip refuses nothing it should and passes what it should not.
PEAK_BYTES_S = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e at 819 GB/s a chip
    # (JAX reports the chip as "TPU v5 lite"; "TPU v5e" is the same part)
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    # No published figure applies to XLA:CPU. Host runs check answers,
    # never rates, and sit orders of magnitude below any accelerator
    # bound; they are held to the v5e row only so that a span timed
    # around nothing is still refused in the CPU tests.
    "cpu": 819e9,
}
# a measured rate implying more than this multiple of the peak sustained
# cannot be a real execution (the result was acknowledged, not computed)
ROOFLINE_SLACK = 2.0


@lru_cache(maxsize=None)
def roofline_bytes_s(device_kind: str | None = None) -> float:
    """The refusal ceiling for ``device_kind`` (default: the kind of the
    first device JAX sees). KeyError names an unknown device."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return ROOFLINE_SLACK * PEAK_BYTES_S[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak bandwidth on record for device kind {device_kind!r}: add "
            "a sourced row to obs/gates.py PEAK_BYTES_S"
        ) from None


def digest(data) -> str:
    """Canonical short fingerprint of a result: ndarray (contiguous bytes)
    or raw bytes — what the watchdog's divergence events and the gen
    byte-diff stream key on."""
    if isinstance(data, (bytes, bytearray, memoryview)):
        raw = bytes(data)
    else:
        raw = np.ascontiguousarray(data).tobytes()
    return hashlib.sha256(raw).hexdigest()[:32]


def roofline_verdict(work_bytes: float, seconds: float) -> dict:
    """Implied sustained HBM traffic of `work_bytes` moved in `seconds`,
    judged against the single-chip bound."""
    implied = work_bytes / seconds
    return {
        "implied_gbps": round(implied / 1e9, 1),
        "roofline_ok": implied <= roofline_bytes_s(),
    }
