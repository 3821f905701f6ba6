"""Seconds of set-up that the program itself filed, from the obs histograms as
they stood when set-up ended (`window.hist_before`): the exact `sum` of every
histogram whose name starts with one of `add`, less those under `less`.
With `of_setup` it is the other side: `setup_s` less that sum, what set-up
spent under none of those names. Neither is cut off at 0: a negative reading
says that seconds were filed twice (a phase inside another, two threads
compiling at once). Quantiles are never read.

The compile listener that files the four phases of a compile (obs/xprof)
leaves an `xla.trace_ms.*` histogram wherever anything compiled, so where
there is one a family without a sample reads 0.0 (a cold run reads nothing
from the cache), and a program without one has nothing to read. The ten
largest histograms summed go to standard error, by name: the name's tail is
the leg the seconds sat under."""

import sys


def _sums(hists, prefixes):
    return {name: h["sum"] / 1e3 for name, h in hists.items() if name.startswith(tuple(prefixes))}


def read(window, params):
    hists = window.hist_before
    if not any(name.startswith("xla.trace_ms.") for name in hists):
        return None
    added, taken = _sums(hists, params["add"]), _sums(hists, params.get("less", ()))
    seconds = sum(added.values()) - sum(taken.values())
    for name, s in sorted(added.items(), key=lambda kv: -kv[1])[:10]:
        print(f"set-up {s:.3f} s: {name} ({hists[name]['count']})", file=sys.stderr)
    return window.setup_seconds - seconds if params.get("of_setup") else seconds
