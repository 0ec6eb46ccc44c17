#!/usr/bin/env python3
"""Readings of a cell's compared numbers for the program and for its
control, on several seeds in one process (the benchmark's own runs never
run the control).

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 5

The control is the plain reference computed one precision below the
configuration's (bfloat16 for float32, float8 weights for bfloat16) in
the program's place. One JSON line per seed: the program's readings (the
comparison a run makes), the control's and, where the path driver has
them, those of the reference with a fault planted. Limits are set
between the largest program reading and the smallest reading that fails
(PERF.md).
"""
import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            run = harness.prepare(harness.ROOT, args.workload, seed,
                                  args.seconds, False, time.perf_counter())
        except harness.Refused as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps(readings(run)), flush=True)
    return 0


def readings(run) -> dict:
    driver = importlib.import_module(
        f"chipbench.drivers.{run.traffic['driver']}")
    st = driver.setup(run)
    counters = driver.window(run, st)
    driver.release(run, st)
    program = {n: v for n, v, _ in driver.check(run, st, counters)}
    out = {"workload": run.name, "seed": run.seed, "program": program,
           "control": driver.control(run, st, counters)}
    if hasattr(driver, "faults"):
        out["faults"] = driver.faults(run, st, counters)
    return out


if __name__ == "__main__":
    sys.exit(main())
