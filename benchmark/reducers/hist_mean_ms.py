"""Mean a service request over the window of the named obs histograms, summed:
each histogram's exact `sum` and `count` after the window less before it. The
histograms' quantiles are never read (log buckets, up to 9 % off). A
histogram that took no sample in the window adds nothing; none at all, and
there is nothing to read."""


def read(window, params):
    total, found = 0.0, False
    for name in params["histograms"]:
        after = window.hist_after.get(name)
        if after is None:
            continue
        before = window.hist_before.get(name, {"sum": 0.0, "count": 0})
        count = after["count"] - before["count"]
        if count > 0:
            total += (after["sum"] - before["sum"]) / count
            found = True
    return total if found else None
