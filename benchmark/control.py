#!/usr/bin/env python3
"""The control of a cell, the program's own readings beside it and the
cell's faults, on several seeds in ONE process (set-up is most of a run's
cost).

    python benchmark/control.py --workload <name> --seeds 11,12,13 --seconds 10 \\
        [--faults first_half_unchecked,second_half_unchecked --fault-seconds 3]

For each seed: a short window at the cell's own size and load, then the
comparison that decides `correct` twice: of the program's answers, and of the
control's answers put in their place (the reference with one stated guarantee
broken; each driver says which). Then one more window for each fault of
benchmark/faults.py that is named, planted in the program. One JSON line a
seed; the control and every fault have to come out as not correct. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import CompileLog, compare, drive, is_correct, load_cell, require_tpu

from benchmark import faults


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    device_kind = require_tpu(cell.chips)
    log = CompileLog().install()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        window, traffic, _ = drive(cell, seed, args.seconds, False, device_kind, log, t0)
        line = {"workload": args.workload, "seed": seed, "requests": window.completed,
                "failed": window.failed}
        readings = [("program", compare(window, traffic)),
                    ("control", compare(window, traffic, control=True))]
        for name in filter(None, args.faults.split(",")):
            with faults.planted(name):
                window, traffic, _ = drive(cell, seed, args.fault_seconds, False, device_kind,
                                           log, time.perf_counter())
            readings.append((name, compare(window, traffic)))
        for who, numbers in readings:
            line[who] = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
            line[f"{who}_correct"] = is_correct(numbers)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
