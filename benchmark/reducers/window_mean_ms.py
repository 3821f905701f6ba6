"""The whole window over all requests completed in it, in milliseconds."""


def read(window, params):
    return window.seconds * 1e3 / window.completed if window.completed else None
