"""Of the traced window's device-idle seconds (the gaps between the programs
of the first device), the share that lies under a NAMED piece of the program:
a leg of a flush (`waterfall.leg`: the legs are those the window's
`serve.stage_ms.device.*` histograms name, so a later leg needs no edit here),
or one of the spans listed under `named`. A gap's seconds go to the program
span with most self time over it (benchmark/host_spans.py), so a gap under a
container span's own time (`serve.dispatch`, `kzg.verify_many`) or under no
span of the program counts as unnamed. A program that has none of the named
spans in its trace leaves nothing to read. The ten longest gaps go to standard
error, each with the three spans that have most self time over it."""

import sys
import time

from benchmark import host_spans, xplane

LEG_PREFIX = "serve.stage_ms.device."


def read(window, params):
    trace = window.trace
    if trace is None or not trace.modules:
        return None
    busy = xplane.device_intervals(trace, 0)
    gaps = [(b1, a2) for (_, b1), (a2, _) in zip(busy, busy[1:]) if a2 > b1]
    if not gaps:
        return None
    legs = {name[len(LEG_PREFIX):] for name in window.hist_after if name.startswith(LEG_PREFIX)}
    named = (legs - {"other"}) | set(params["named"])
    t_read = time.perf_counter()
    threads = [host_spans.innermost(rows)
               for rows in host_spans.read_host_spans(host_spans.TRACE_DIR, params["spans"])]
    print(f"trace: host planes read in {time.perf_counter() - t_read:.1f} s", file=sys.stderr)
    if not any(name in named for rows in threads for name, _, _ in rows):
        return None
    rows = host_spans.name_gaps(gaps, threads, set(params["waits"]))
    for span, seconds, self_s in sorted(rows, key=lambda r: -r[1])[:10]:
        most = sorted(self_s.items(), key=lambda kv: -kv[1])[:3]
        print(f"idle gap {seconds:.6f} s{'' if span in named else ', unnamed'}: "
              + (", ".join(f"{name} {s:.6f}" for name, s in most) or "no span of the program"),
              file=sys.stderr)
    return 100.0 * sum(s for span, s, _ in rows if span in named) / sum(s for _, s, _ in rows)
