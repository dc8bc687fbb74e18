#!/usr/bin/env python3
"""The readings that `correct`'s limits are set from, on the chip, at a
cell's own size: for each seed one short window of the cell's own load, then
the same comparison a run makes, and beside it the controls (the reference
computed in a lower precision and put in the program's place).

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 \
        --seconds 14 --controls fp8,bf16 [--control-seeds 3]

One process for all the seeds, so the chip is reached once and each program
is traced once; the controls are read on the first `--control-seeds` seeds.
The benchmark's own runs never call this; PERF.md holds what it read.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=14.0)
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--control-seeds", type=int, default=None)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run as brun
    from benchmark.drivers import train_steps
    train_steps.compiled_step = functools.lru_cache(maxsize=None)(
        train_steps.compiled_step)

    spec, peaks, devices, _ = brun.open_cell(ROOT, args.workload)
    controls = tuple(c for c in args.controls.split(",") if c)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"readings_{args.workload}.jsonl")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        controlled = args.control_seeds is None or i < args.control_seeds
        result = brun.run_cell(spec, seed, args.seconds, False, devices,
                               peaks, controls=controls if controlled else ())
        line = {"seed": seed, "correct": result["correct"],
                "compared": result["compared"],
                "metrics": result["metrics"]}
        print("READING " + json.dumps(line), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
