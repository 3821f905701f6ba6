"""Published peaks of the chips the benchmark may divide by, keyed by JAX's
`device_kind`. Source: Google Cloud documentation, "TPU v5e" system
architecture page (16 GB HBM2e at 819 GB/s per chip). A device that is not
here is an error, never a default: there is no `cpu` row."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no sourced peak {what!r} for device kind {device_kind!r}") from None
