"""Device milliseconds a benchmark request of the XLA programs whose name
matches `pattern`, from the traced part of the window. Nothing matching:
nothing to read. More than one distinct program matching: an error, since
the name then stands for several kernels."""

from benchmark import xplane


def read(window, params):
    if window.trace is None or not window.trace.requests:
        return None
    seconds, executions = xplane.program_seconds(window.trace, params["pattern"])
    if not executions:
        return None
    return seconds * 1e3 / window.trace.requests
