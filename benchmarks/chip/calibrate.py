"""Readings from which each cell's limits are set, many seeds a process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,3 --seconds 1 [--control | --fault <name>]

For each seed it runs the cell as ``run.py`` does (set-up, a short window,
the check) and prints one JSON line with the compared numbers: those of
the program, of the control (``--control``: the reference in the
precision below the configuration's, in the program's place), or of the
program with a fault planted (``--fault``, see ``faults.py``). Not part
of a benchmark run; it needs the cell's chips as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run as harness
import common


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.ROOT / ".jax_cache")
    spec = common.load_cell(args.workload)
    peaks = common.load_json(harness.HERE / "peaks.json")
    try:
        device = harness.device_info(spec["cell"]["chips"], peaks)
    except harness.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 1
    driver = common.load_module("drivers", spec["traffic"]["driver"])
    mode = ("control" if args.control
            else f"fault:{args.fault}" if args.fault else "program")
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.fault:
            import faults

            with faults.planted(args.fault, driver):
                res = harness.run_cell(spec, seed, args.seconds, False,
                                       peaks=peaks, device=device,
                                       log=lambda s: None)
        else:
            res = harness.run_cell(spec, seed, args.seconds, False,
                                   peaks=peaks, device=device,
                                   control=args.control, log=lambda s: None)
        print(json.dumps({"workload": args.workload, "mode": mode,
                          "seed": seed, "correct": res["correct"],
                          "readings": {**res["not_compared"], **{
                              k: v["value"] for k, v in
                              res["checks"].items()}}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
