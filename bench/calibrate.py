"""Readings from which a cell's limits are set (see PERF.md): for each seed,
the numbers the cell compares, read for the program, for the control (the
reference at the precision below the configuration's, in the program's
place) and for the faults the driver plants. One process, many seeds.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3

Prints one JSON line per seed. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    reg = harness.Registry()
    w = reg.workload(args.workload)
    config, traffic = reg.config(w["config"]), reg.traffic(w["traffic"])
    device = run.find_device(w["chips"])
    run.configure_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Ctx(cell=w["name"], config=config, traffic=traffic,
                          model=reg.model(config), seed=seed, seconds=0.0, trace=False,
                          t_start=t, chips=w["chips"], device_kind=device["kind"])
        out = reg.driver(traffic).readings(ctx)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
