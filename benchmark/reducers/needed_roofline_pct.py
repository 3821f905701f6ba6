"""The least time the chip's memory system needs for a request (the bytes the
ALGORITHM must move, from shapes, by the function `bytes` of the module
`module` beside benchmark/needed.py, given the configuration's `sizes`, over
the sourced HBM peak) as a share of the device time of the program that did
it (the metric named by `of`). Only the bytes leg, as hbm_roofline_pct."""

import importlib

from benchmark.peaks import peak


def read(window, params):
    kernel_ms = window.metric(params["of"])
    if not kernel_ms:
        return None
    needed = importlib.import_module(f"benchmark.{params['module']}")
    sizes = [int(window.cell.config[key]) for key in params["sizes"]]
    least_s = getattr(needed, params["bytes"])(*sizes) / peak(window.device_kind, "hbm_bytes_per_s")
    return 100.0 * least_s / (kernel_ms / 1e3)
