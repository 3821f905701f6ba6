#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in BENCHMARK.json names its configuration and its traffic
file; the traffic file names the driver under benchmark/traffic/; each metric
the cell reports has a file under benchmark/metrics/ that names its reader
under benchmark/reducers/. Nothing here lists a cell, a configuration or a
metric. The run starts one in-process VerifyService as the configuration
states it, lets the driver make its inputs from the seed and warm up its own
shapes (set-up), drives the service in a closed loop for `--seconds`, and
only then frees the service and compares every answer of the window with the
plain reference. The last line of standard output is the result.

Exits non-zero, printing no result, when JAX finds no TPU or another number
of chips than the cell asks for: there is no CPU fallback on this path.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402
from benchmark.compile_log import CompileLog  # noqa: E402

HERE = os.path.join(ROOT, "benchmark")
# a request answered from one of these counts as failed: the service came
# through, the device path did not
FALLBACK_COUNTERS = ("serve.degraded_items", "slot.forest_rebuilds", "fault.degraded")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# profiler sessions a traced run may take before it gives up on the device
TRACE_ATTEMPTS = 3


class BenchError(Exception):
    pass


# --------------------------------------------------------------- manifest --


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """A workload of BENCHMARK.json with the files its names lead to."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def reported(manifest: dict, kind: str, workload: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics that the cell reports."""
    return [m for m in manifest[kind] if "workloads" not in m or workload in m["workloads"]]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchError(f"BENCHMARK.json has no workload {workload!r}")
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=load_json(os.path.join(root, config_entry["file"])),
        traffic=load_json(os.path.join(root, "benchmark", "workloads", f"{workload}.json")),
        end_to_end=reported(manifest, "end_to_end", workload),
        per_layer=reported(manifest, "per_layer", workload),
    )


def load_metric(name: str) -> dict:
    return load_json(os.path.join(HERE, "metrics", f"{name}.json"))


# ----------------------------------------------------------------- window --


@dataclass
class Window:
    """What one measured window left behind, for the metrics' readers."""

    cell: Cell
    device_kind: str
    setup_seconds: float
    seconds: float = 0.0  # first submit to the last verdict
    latencies_ms: list[float] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    hist_before: dict = field(default_factory=dict)
    hist_after: dict = field(default_factory=dict)
    compiles: dict = field(default_factory=dict)
    trace: xplane.Trace | None = None
    traced_from: int = 0  # the first request the trace holds

    @property
    def completed(self) -> int:
        return len(self.latencies_ms)

    def metric(self, name: str):
        """The value the named metric's reader finds, or None."""
        spec = load_metric(name)
        reader = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
        return reader.read(self, spec.get("params", {}))


def _fallbacks(counters: dict) -> int:
    return int(sum(
        v for k, v in counters.items()
        if k in FALLBACK_COUNTERS or k.startswith("fault.degraded.")
    ))


def traces_a_device() -> bool:
    """Whether the profiler's trace has a device plane at all: the CPU's has
    none, so there a second session could find nothing the first missed."""
    import jax

    return jax.default_backend() != "cpu"


def start_trace() -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    # the limb programs' HLO is tens of MB: copied into the trace it makes
    # stop_trace take minutes, and no reader here needs it
    options.enable_hlo_proto = False
    shutil.rmtree(TRACE_DIR, ignore_errors=True)  # one trace on disk, at a fixed path
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)


def stop_trace(window: Window, since: tuple[float, int]) -> xplane.Trace:
    """The trace of the window's requests from `since` (seconds, requests
    completed) on, written and read back."""
    import jax

    t_stop = time.perf_counter()
    jax.profiler.stop_trace()
    t_read = time.perf_counter()
    trace = xplane.read_xplane(TRACE_DIR, window.seconds - since[0], window.completed - since[1])
    print(f"trace: stopped and written in {t_read - t_stop:.1f} s, "
          f"read in {time.perf_counter() - t_read:.1f} s", file=sys.stderr)
    return trace


def measure(svc, traffic, window: Window, seconds: float, log: CompileLog,
            trace_requests: int = 0) -> None:
    """The closed loop: one client, the next request when the last one's
    verdict is back. The window closes when the last request begun inside
    `seconds` resolves. With `trace_requests` the profiler runs over the
    first so many requests; the seconds it takes to stop and to be read are
    not counted. A trace in which no device ran an operation (the profiler
    now and then misses a short request's device) is taken again over the
    next so many requests, up to TRACE_ATTEMPTS times."""
    from eth_consensus_specs_tpu import obs

    before = obs.snapshot()
    window.hist_before = before.get("histograms", {})
    compiled_before = log.mark()
    attempts = (TRACE_ATTEMPTS if traces_a_device() else 1) if trace_requests > 0 else 0
    tracing = attempts > 0
    if tracing:
        start_trace()
    since = (0.0, 0)  # (seconds, requests) of the window where the trace began
    prepared = getattr(traffic, "prepared", None)  # requests a driver can make at all
    t0 = time.perf_counter()
    while True:
        t1 = time.perf_counter()
        if t1 - t0 >= seconds or window.attempted == prepared:
            break
        window.attempted += 1
        try:
            traffic.request(svc, window.attempted - 1)
        except Exception as exc:  # noqa: BLE001 — a refused or failed request is counted, not fatal
            window.failed += 1
            print(f"request {window.attempted - 1} failed: {exc!r}", file=sys.stderr)
        else:
            window.latencies_ms.append((time.perf_counter() - t1) * 1e3)
        window.seconds = time.perf_counter() - t0
        if tracing and window.completed - since[1] >= trace_requests:
            t_stop = time.perf_counter()
            window.trace, window.traced_from = stop_trace(window, since), since[1]
            attempts -= 1
            tracing = not window.trace.modules and attempts > 0
            if tracing:
                print(f"trace: no device ran an operation in requests {since[1]} to "
                      f"{window.completed - 1}; tracing the next {trace_requests}", file=sys.stderr)
                start_trace()
                since = (window.seconds, window.completed)
            # writing and reading the trace is no request's time: the window
            # goes on as long as an untraced one, over the same requests
            t0 += time.perf_counter() - t_stop
    if tracing:  # the window closed before that many requests
        window.trace, window.traced_from = stop_trace(window, since), since[1]
    after = obs.snapshot()
    window.hist_after = after.get("histograms", {})
    window.compiles = CompileLog.since(compiled_before, log.mark())
    window.failed += _fallbacks(after["counters"]) - _fallbacks(before["counters"])


# ----------------------------------------------------------------- result --


def read_metrics(window: Window, specs: list[dict]) -> dict:
    out = {}
    for spec in specs:
        value = window.metric(spec["name"])
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def device_report(window: Window) -> dict:
    import jax

    devices = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }
    if window.trace is not None:
        busy = xplane.busy_seconds(window.trace)
        if busy is not None:
            report["busy_s"] = busy
        report["window_s"] = window.trace.window_s
    return report


def is_correct(compared: dict) -> bool:
    return all(value <= limit for value, limit in compared.values())


def result_line(window: Window, traced: bool, compared: dict, device: dict) -> dict:
    specs = window.cell.per_layer if traced else window.cell.end_to_end
    line = {
        "correct": is_correct(compared),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": read_metrics(window, specs),
        "device": device,
    }
    if traced and window.trace is not None and window.trace.modules:
        line["breakdown"] = {
            "device_ops": xplane.top_device_ops(window.trace),
            "idle_gaps": xplane.idle_gaps(window.trace),
        }
    line["window"] = {"seconds": window.seconds, "completed": window.completed,
                      **window.compiles,
                      **({"traced_from": window.traced_from} if window.trace is not None else {}),
                      "request_ms": [round(ms, 1) for ms in window.latencies_ms]}
    line["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    return line


def drive(cell: Cell, seed: int, seconds: float, traced: bool, device_kind: str,
          log: CompileLog, t_start: float):
    """Set-up and the window: (window, traffic, device report). The service
    is closed and the driver's device state freed when this returns, so the
    reference may run."""
    from eth_consensus_specs_tpu.serve.config import ServeConfig
    from eth_consensus_specs_tpu.serve.service import VerifyService

    driver = importlib.import_module(f"benchmark.traffic.{cell.traffic['driver']}")
    traffic = driver.Traffic(cell.config, cell.traffic.get("params", {}), seed)
    t_service = time.perf_counter()
    svc = VerifyService(ServeConfig(**cell.config["serve_config"]), name="bench")
    try:
        traffic.setup(svc)
        window = Window(cell, device_kind, setup_seconds=time.perf_counter() - t_start)
        print(f"set-up: {t_service - t_start:.1f} s to the service's start (imports, device), "
              f"{time.perf_counter() - t_service:.1f} s of inputs and warm-up; {log.mark()}",
              file=sys.stderr)
        trace_requests = int(cell.traffic.get("trace_requests", 0)) if traced else 0
        measure(svc, traffic, window, seconds, log, trace_requests)
        device = device_report(window)
    finally:
        svc.close()
    traffic.release()
    return window, traffic, device


def compare(window: Window, traffic, control: bool = False) -> dict:
    """Every number compared, with its limit. The reference runs here: after
    the window, the peak read, the service's state freed."""
    compared = traffic.compare(control=control)
    # an answer that never came; one that came late is late, not wrong
    compared["unanswered"] = (window.attempted - window.completed, 0)
    return compared


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, device_kind: str,
             log: CompileLog, t_start: float) -> dict:
    """Everything after the device check; the tests drive this on the CPU."""
    window, traffic, device = drive(cell, seed, seconds, traced, device_kind, log, t_start)
    return result_line(window, traced, compare(window, traffic), device)


def require_tpu(chips: int) -> str:
    import jax

    devices = jax.devices()
    found = f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})"
    if devices[0].platform != "tpu":
        raise BenchError(f"no accelerator: JAX found {found}")
    if len(devices) != chips:
        raise BenchError(f"the cell asks for {chips} chip(s), JAX found {found}")
    from benchmark.peaks import peak

    peak(devices[0].device_kind, "hbm_bytes_per_s")  # an unknown chip is an error
    return devices[0].device_kind


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        device_kind = require_tpu(cell.chips)
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device_kind,
                        CompileLog().install(), _T_START)
    except (BenchError, xplane.TraceError, FileNotFoundError, KeyError) as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    print(f"window: {line['window']}", file=sys.stderr)
    for name, entry in line["compared"].items():
        print(f"compared {name}: {entry['value']} (limit {entry['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
