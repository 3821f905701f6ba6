#!/usr/bin/env python3
"""Compile, for ONE described v5e device and without a chip, every device
program ``chip_smoke.py`` dispatches, at exactly the shapes it dispatches
them (``analysis/chip_programs.py``), and print one JSON line a program:
seconds to lower, seconds to compile, generated-code bytes and the
compiler's memory analysis.

    JAX_PLATFORMS=cpu python scripts/tpu_compile_inventory.py [--out FILE]
        [--only NAME ...] [--no-limb] [--validators N]

The sum of the compile seconds is the smoke's cold start on the chip, so
this runs BEFORE any chip call (on-chip-measurement guide, section 2): what
the chip's compiler refuses, or takes minutes over, costs nothing here.
Only one process at a time may load the chip's library in the sandbox, so
the programs compile one after another in this one process. Nothing runs
on a device, and a cache filled here cannot be read back on a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str] | None = None) -> int:
    import chip_smoke

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--validators", type=int, default=chip_smoke.MAINNET.validators)
    ap.add_argument("--only", nargs="*", default=None, help="program names to compile")
    ap.add_argument("--no-limb", action="store_true", help="skip the curve-arithmetic kernels")
    ap.add_argument("--out", default=None, help="also append the JSON lines to this file")
    ap.add_argument("--mesh", action="store_true",
                    help="instead: the two programs of `chip_smoke.py --chips 4`, "
                    "for a mesh of the four described chips")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from eth_consensus_specs_tpu.analysis import chip_programs

    # the compiles here can never be read back without a chip: keep them
    # out of the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    sizes = chip_smoke.MAINNET._replace(validators=args.validators)
    total = 0.0
    failed = 0
    if args.mesh:
        programs = chip_programs.mesh_programs(
            topo.devices, chip_smoke.MESH_TREES, chip_smoke.MESH_TREE_DEPTH,
            chip_smoke.MESH_VALIDATORS, chip_smoke.MESH_STEP_DEPTH,
        )
    else:
        programs = chip_programs.slot_programs(*sizes)
    for prog in programs:
        if args.only is not None and prog.name not in args.only:
            continue
        if args.no_limb and prog.limb:
            continue
        try:
            # mesh programs carry their own NamedShardings
            row = chip_programs.compile_for(None if args.mesh else one_chip, prog)
        except Exception as exc:  # noqa: BLE001 — the refusal IS the finding
            row = {"program": prog.name, "refused": repr(exc)[:2000]}
            failed += 1
        total += row.get("lower_s", 0.0) + row.get("compile_s", 0.0)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(line + "\n")
    print(json.dumps({"total_lower_plus_compile_s": round(total, 1), "refused": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
