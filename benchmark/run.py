#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data. `BENCHMARK.json` names the cell's
configuration and traffic; `benchmark/configs/<config>.json` holds the
model's constructor arguments, `benchmark/traffic/<traffic>.json` the load and
the name of its driver (`benchmark/drivers/<driver>.py`), and each per-layer
metric has a reader `benchmark/layer_metrics/<base name>.py`.

A run: look for the chip (none, too few, or a kind that `peaks.json` does not
list: exit 2, no result), draw the weights on the device from the seed, warm
the cell's own shapes (all of that is `setup_s`), drive the window, read the
peak memory, free the program's state, compare a sample of what the window
produced with the plain reference (`reference.py`), and print one JSON line.
With `--trace 1` the last seconds of the window are profiled and the line
carries the cell's per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # "process start", as near as Python gets

import argparse                    # noqa: E402
import contextlib                  # noqa: E402
import gc                          # noqa: E402
import importlib                   # noqa: E402
import importlib.metadata          # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:            # run as a script: make `benchmark` importable
    sys.path.insert(0, ROOT)

from benchmark.report import say    # noqa: E402
HOST_ANNOTATIONS = ("submit", "wait", "input_prep")


def load_cell(root: str, workload: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    in_cell = lambda m: cell["name"] in m.get("workloads", [cell["name"]])
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in manifest["end_to_end"] if in_cell(m)],
            "per_layer": [m for m in manifest["per_layer"] if in_cell(m)]}


def load_peaks() -> dict:
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        return json.load(f)


def require_chip(jax, chips: int, peaks: dict):
    """The devices to use, or exit 2 with no result: no TPU, fewer chips than
    the cell asks for, or a device kind with no recorded peak."""
    devices = jax.devices()
    problem = None
    if devices[0].platform != "tpu":
        problem = f"JAX gives platform {devices[0].platform!r}, not a TPU"
    elif len(devices) < chips:
        problem = f"the cell needs {chips} chip(s), JAX gives {len(devices)}"
    elif devices[0].device_kind not in peaks:
        problem = (f"no peak recorded for device kind "
                   f"{devices[0].device_kind!r} in benchmark/peaks.json")
    if problem:
        print(f"run.py: {problem}; no result", file=sys.stderr, flush=True)
        raise SystemExit(2)
    return devices[:chips]


def open_cell(root: str, workload: str, devices=None):
    """(spec, peaks, devices, compile cache directory): the cell's data, the
    chip looked for (unless `devices` are handed in) and the compile cache on,
    in the order every entry point needs them."""
    spec, peaks = load_cell(root, workload), load_peaks()
    import jax
    if devices is None:
        devices = require_chip(jax, spec["cell"]["chips"], peaks)
    from alphafold2_tpu.runtime import enable_compile_cache
    return spec, peaks, devices, enable_compile_cache()


def build_model(config: dict):
    """The program's model from the configuration file: every key that is a
    field of `Alphafold2` is a constructor argument."""
    import jax.numpy as jnp
    from alphafold2_tpu import Alphafold2
    fields = Alphafold2.__dataclass_fields__
    kwargs = {k: v for k, v in config.items()
              if k in fields and k != "dtype"}
    return Alphafold2(dtype=jnp.dtype(config["dtype"]), **kwargs)


class Run:
    """What a driver is handed: the cell's data, the model and weights, and
    the harness's hooks for host annotations and the profiler."""

    def __init__(self, spec, seed, seconds, trace, devices, peaks):
        self.cell, self.config = spec["cell"], spec["config"]
        self.traffic = spec["traffic"]
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.devices, self.peaks = devices, peaks
        self.model = self.params = None
        self.trace_dir = os.path.join(ROOT, ".bench_trace",
                                      self.cell["name"])
        self._tracing = self._window_mark = None
        self.compile_times = []

    def annotate(self, name: str):
        """A host span the profiler sees (and nothing when it is off)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def tick(self, elapsed: float):
        """Drivers call this from their loop: starts the profiler once the
        window has `trace_seconds` left."""
        if not self.trace or self._tracing is not None:
            return
        lead = float(self.traffic.get("trace_seconds", 5))
        if elapsed < self.seconds - lead:
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        from benchmark.trace_reduce import WINDOW
        self._window_mark = jax.profiler.TraceAnnotation(WINDOW)
        self._window_mark.__enter__()
        self._tracing = True

    def close_trace(self):
        """Stops the profiler (after the window) and reduces the trace."""
        if not self._tracing:
            return None
        import jax
        from benchmark import trace_reduce
        self._window_mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False
        path = trace_reduce.find_xplane(self.trace_dir)
        reduced = trace_reduce.reduce_xplane(path, HOST_ANNOTATIONS) \
            if path else None
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return reduced


def _versions() -> dict:
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _memory_peak(devices) -> int:
    """Peak device memory on the fullest chip. This runtime keeps two books:
    `peak_bytes_in_use` holds live arrays only (arguments, outputs, weights),
    and what a running program needs for its temporaries is reserved apart
    (`peak_bytes_reserved`: a probe's 1.5 GiB of temporaries showed there and
    nowhere else, my chip run, PR 25). A program's arguments are live while
    its temporaries are reserved, so the peak is the sum of the two."""
    def peak(d):
        stats = d.memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) \
            + stats.get("peak_bytes_reserved", 0)
    return int(max(peak(d) for d in devices))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, controls=()) -> dict:
    """Set-up, window, check: the result line as a dict. `controls` adds
    lower-precision controls to the check (`readings.py` only)."""
    import jax
    from benchmark import weights

    run = Run(spec, seed, seconds, trace, devices, peaks)
    compile_times = run.compile_times     # the listener outlives the run
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_times.append(time.perf_counter())
        if event.endswith("backend_compile_duration")
        or "cache_retrieval" in event else None)

    t = time.perf_counter()
    run.model = build_model(run.config)
    run.params = weights.make_params(run.model, seed)
    say(phase="weights", seconds=time.perf_counter() - t,
        params=sum(int(x.size) for x in jax.tree.leaves(run.params)))

    driver_mod = importlib.import_module(
        "benchmark.drivers." + run.traffic["driver"])
    driver = driver_mod.Driver(run)
    t = time.perf_counter()
    driver.warm()
    # Tracing the model leaves millions of small objects behind, and a full
    # collection over them stops every Python thread, the scheduler's among
    # them. Collect now, as set-up, and keep the survivors out of later
    # collections (what `timeit` does by turning the collector off).
    gc.collect()
    gc.freeze()
    say(phase="warm", seconds=time.perf_counter() - t,
        programs=len(run.compile_times))
    setup_s = time.perf_counter() - _T0

    t_open = time.perf_counter()
    obs = driver.window()
    t_close = time.perf_counter()
    reduced = run.close_trace()
    memory_peak = _memory_peak(devices)
    compiles = sum(t_open <= c <= t_close for c in run.compile_times)
    say(phase="window", window_s=obs["window_s"],
        attempted=obs["attempted"], failed=obs["failed"],
        compiles_in_window=compiles, memory_peak_bytes=memory_peak,
        **obs.get("notes", {}))

    driver.release()
    t = time.perf_counter()
    compared = driver.check(("f32",) + tuple(controls))
    say(phase="check", seconds=time.perf_counter() - t)
    compared["failed"] = [obs["failed"], 0]
    compared["compiles_in_window"] = [compiles, 0]

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        wanted, values = spec["per_layer"], {}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        for m in wanted:
            reader = importlib.import_module(
                "benchmark.layer_metrics." + m["name"].split(".")[0])
            value = reader.read(obs.get("spans", []), obs.get("snapshot", {}),
                                reduced, dict(obs, run=run, metric=m))
            if value is not None:
                values[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        wanted = spec["end_to_end"]
        e2e = dict(obs["end_to_end"], setup_s=setup_s)
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in wanted}

    within = {k: v for k, v in compared.items() if not k.startswith("control")}
    result = {"correct": all(v <= lim for v, lim in within.values()),
              "attempted": obs["attempted"], "failed": obs["failed"],
              "metrics": values, "device": device}
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    return result


def main(argv=None, devices=None, root=ROOT) -> int:
    """`devices` and `root`: the tests hand in the CPU's devices and a
    directory of tiny cells, to drive everything but the look for a chip;
    the command line never does."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, peaks, devices, cache_dir = open_cell(root, args.workload, devices)
    say(phase="environment", versions=_versions(), seed=args.seed,
        workload=args.workload, compile_cache_dir=cache_dir,
        device={"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)})

    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices, peaks)
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value:.6g} (limit {limit:.6g})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
