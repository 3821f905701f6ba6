"""1 - (union of the device's operation intervals) / (traced window)."""

from benchmark import xplane


def read(window, params):
    if window.trace is None or not window.trace.window_s:
        return None
    busy = xplane.busy_seconds(window.trace)
    if not busy:
        return None
    return 100.0 * (1.0 - busy / window.trace.window_s)
