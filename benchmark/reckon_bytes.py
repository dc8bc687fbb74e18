#!/usr/bin/env python3
"""Reckon each cell's device memory with no chip: compile the cell's largest
program for a DESCRIBED v5e (nothing runs, nothing is timed) and print what
the compiler says it needs, as a share of the chip's `bytes_limit`.

    JAX_PLATFORMS=cpu python3 benchmark/reckon_bytes.py [--workload <name>]
                                                       [--reference]

Run by hand; its output goes into PERF.md. `--reference` also compiles the
plain reference at the cell's longest request, to see that the check fits.
A driver says what its largest program is, and what the check's is
(`largest_program` and `reference_program` in its file).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BYTES_LIMIT = 15.75 * 2 ** 30      # memory_stats()["bytes_limit"], PR 22


def report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    parts = {"temp": m.temp_size_in_bytes,
             "arguments": m.argument_size_in_bytes,
             "outputs": m.output_size_in_bytes,
             "aliased": m.alias_size_in_bytes}
    total = parts["temp"] + parts["arguments"] + parts["outputs"] \
        - parts["aliased"]
    line = {"program": name, **{k: round(v / 2 ** 30, 3)
                                for k, v in parts.items()},
            "total_gib": round(total / 2 ** 30, 3),
            "share_of_bytes_limit": round(total / BYTES_LIMIT, 3)}
    print(json.dumps(line), flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark import weights
    from benchmark.run import build_model, load_cell

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                      sharding=one_chip)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [c["name"] for c in json.load(f)["workloads"]]
    for name in [args.workload] if args.workload else names:
        spec = load_cell(ROOT, name)
        config, traffic = spec["config"], spec["traffic"]
        model = build_model(config)
        shapes = jax.tree.map(lambda s: place(s.shape, s.dtype),
                              weights.param_shapes(model))
        driver = importlib.import_module(
            "benchmark.drivers." + traffic["driver"])
        label, fn, fn_args = driver.largest_program(
            model, shapes, config, traffic, place)
        report(f"{name}: {label}", fn.lower(*fn_args).compile())
        if args.reference:
            label, fn, fn_args = driver.reference_program(
                shapes, config, traffic, place)
            report(f"{name}: {label}", fn.lower(*fn_args).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
