"""The cells of BENCHMARK.json cut to a size a CPU test can hold: the same
drivers, files and harness, 64 validators, four sidecars a block."""

from __future__ import annotations

import os
import time

from benchmark import run
from benchmark.compile_log import CompileLog

ROOT = run.ROOT


def tiny_cell(name: str) -> run.Cell:
    """`name` is a workload file under benchmark/workloads/; the slot cell is
    not in BENCHMARK.json, so its entry is built here."""
    manifest = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    traffic = run.load_json(os.path.join(ROOT, "benchmark", "workloads", f"{name}.json"))
    config = run.load_json(os.path.join(ROOT, "benchmark", "configs", f"{traffic['config']}.json"))
    if "validators" in config:
        config["validators"] = 64
        config["serve_config"]["slot_validators"] = 64
    if "blobs_per_block" in config:
        config["blobs_per_block"] = 4
        config["serve_config"]["max_batch"] = 4
    params = traffic["params"]
    if traffic["driver"] == "blob_block":
        params.update(pool_blobs=6, invalid_first=0, invalid_every=1)
    if traffic["driver"] == "slot":
        params.update(slots_prepared=2, committees=2, committee_size=1, sync_size=64, blobs=2)
    return run.Cell(
        name=name, chips=1, config=config, traffic=traffic,
        end_to_end=run.reported(manifest, "end_to_end", name),
        per_layer=run.reported(manifest, "per_layer", name),
    )


def drive(name: str, seed: int, seconds: float, traced: bool = False):
    """(window, traffic, device) of one tiny run, the chip check skipped."""
    return run.drive(tiny_cell(name), seed, seconds, traced, "cpu", CompileLog().install(),
                     time.perf_counter())
