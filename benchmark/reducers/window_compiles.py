"""XLA backend compiles inside the window less persistent-cache hits, a
benchmark request: what was compiled anew while requests were timed. The
benchmark warms every shape before the window, so what is left is what the
program compiles again in steady state. Expected 0."""


def read(window, params):
    if not window.compiles or not window.completed:
        return None
    return (window.compiles["compiles"] - window.compiles["cache_hits"]) / window.completed
