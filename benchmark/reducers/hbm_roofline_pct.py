"""The least time the chip's memory system needs for a request (the bytes the
ALGORITHM must move, from shapes, by a function of benchmark/needed.py, over
the sourced HBM peak) as a share of the device time of the program that did
it (the metric named by `of`). Only the bytes leg: no integer-op peak of the
chip is sourced, so the true roofline share is this or more."""

from benchmark import needed
from benchmark.peaks import peak


def read(window, params):
    kernel_ms = window.metric(params["of"])
    if not kernel_ms:
        return None
    least_bytes = getattr(needed, params["bytes"])(int(window.cell.config[params["size"]]))
    least_s = least_bytes / peak(window.device_kind, "hbm_bytes_per_s")
    return 100.0 * least_s / (kernel_ms / 1e3)
