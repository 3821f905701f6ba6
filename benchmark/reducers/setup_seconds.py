"""Process start to the first timed request: imports, the C cores, inputs
from the seed, compile or cache load, warm-up."""


def read(window, params):
    return window.setup_seconds
