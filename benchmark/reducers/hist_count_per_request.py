"""Samples the named obs histogram took in the window (its exact `count`
after the window less before it), a benchmark request. A histogram that took
none reads 0.0 where the histogram named by `present` took some: the place
was reached and nothing happened there. Where that one took none either,
there is nothing to read."""


def _samples(window, name):
    after = window.hist_after.get(name)
    if after is None:
        return 0
    return after["count"] - window.hist_before.get(name, {"count": 0})["count"]


def read(window, params):
    if not window.completed:
        return None
    count = _samples(window, params["histogram"])
    if count == 0 and _samples(window, params["present"]) == 0:
        return None
    return count / window.completed
