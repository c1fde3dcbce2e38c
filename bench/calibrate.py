#!/usr/bin/env python3
"""Read the compared numbers of a cell over many seeds in one process, for
the program and for its control, to set the limits that decide ``correct``.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 101,102,... --control-seeds 201,202,203

Each seed is a short run of the cell at its own size and load, set up in
this one process (so the set-up that later seeds pay is small). Prints one
line per run, ``program`` or ``control``, with each number compared, and
last one JSON object with the largest program reading and the smallest
control reading of each number. Not a benchmark run: the timings it prints
are not results.
"""

import argparse
import json
import sys
import time

import run

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = run.tpu_devices(cell.chips)
    if devices is None:
        return 2
    run.enable_compile_cache()
    readings: dict[str, dict[str, list[float]]] = {"program": {},
                                                   "control": {}}
    plan = ([("program", int(s)) for s in args.seeds.split(",")]
            + [("control", int(s)) for s in args.control_seeds.split(",")
               if s])
    for side, seed in plan:
        out = json.loads(run.run_cell(
            cell, devices, seed=seed, seconds=args.seconds, trace=False,
            control=side == "control", t_start=time.perf_counter()))
        nums = {k: v["value"] for k, v in out["checks"].items()}
        print(f"{side} seed {seed}: correct={out['correct']} "
              f"attempted={out['attempted']} {nums}", flush=True)
        for k, v in nums.items():
            readings[side].setdefault(k, []).append(float(v))
    print(json.dumps({
        "workload": cell.name,
        "program_max": {k: max(v) for k, v in readings["program"].items()},
        "control_min": {k: min(v) for k, v in readings["control"].items()},
        "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
