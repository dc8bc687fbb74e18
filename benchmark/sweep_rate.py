#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip: the same cell at several
fixed arrival rates in one process, each for a short window, printing for
each rate the latencies and whether a backlog was left when arrivals stopped.
The highest rate that leaves none is the knee; the cell's traffic file then
fixes its rate at four fifths of it. The benchmark's own runs never search.

    python3 benchmark/sweep_rate.py --workload online_fold_short \
        --rates 4,8,12,16 --seconds 20 --seed 1
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run as brun, weights

    spec, peaks, devices, _ = brun.open_cell(ROOT, args.workload)
    model = brun.build_model(spec["config"])
    params = weights.make_params(model, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        spec["traffic"] = dict(spec["traffic"], rate_per_s=rate)
        run = brun.Run(spec, args.seed, args.seconds, False, devices, peaks)
        run.model, run.params = model, params
        driver = importlib.import_module(
            "benchmark.drivers." + run.traffic["driver"]).Driver(run)
        driver.warm()
        obs = driver.window()
        driver.release()
        snap = obs["snapshot"]
        print("SWEEP " + json.dumps({
            "rate_per_s": rate, **obs["end_to_end"], **obs["notes"],
            "failed": obs["failed"], "batches": snap["batches"],
            "served": snap["served"],
            "exec_busy_share": snap["exec_busy_s"] / args.seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
