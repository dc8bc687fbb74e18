"""Kernels: device milliseconds per execution of the cell's dearest program
in one kernel of the program's own vocabulary (`alphafold2_tpu.obs.device`:
`kernel_ms.<kernel>.bulk`, `kernel_ms.<kernel>.train`). The eight kernels sum
to the execution's busy time.

A property of the program at a shape, not of the window: the harness removes
the window's trace before readers run, and the join from a device event to
its `op_name` needs the executable, which `driver.release()` has dropped by
then. So one capture of its own, after the window, of one program, driven
through the program's own path: for the fold cells a fresh
`serve.FoldExecutor` at the longest bucket, `max_batch_size` rows, the
configuration's MSA depth and recycles, on a zero batch as `warmup` uses; for
the training cell the jitted `train.make_train_step` with a donated copy of
the state at the traffic's crop and batch. Made once a run and shared by the
kernels' entries."""

import functools
import time

REPEATS = 1       # device times repeat to a part in a thousand


def _fold_profile(run):
    from alphafold2_tpu import serve
    t = run.traffic
    executor = serve.FoldExecutor(run.model, run.params)
    return executor.profile(
        (max(t["buckets"]), t["max_batch_size"], run.config["msa_depth"],
         run.config["num_recycles"]), repeats=REPEATS)


def _train_profile(run):
    import jax
    from alphafold2_tpu.obs import device
    from benchmark.drivers import train_steps
    driver = train_steps.Driver(run)      # a donated copy of the state
    batch = driver.feed(0)
    step = driver.step.lower(driver.state, batch).compile()

    def call():           # the step donates its state: keep the new one
        driver.state, metrics = step(driver.state, batch)
        jax.block_until_ready(metrics["loss"])

    return device.profile(step, call, repeats=REPEATS)


@functools.lru_cache(maxsize=1)
def _profile(run):
    """The run's one capture, or None where the program has no reducer (a
    parent commit)."""
    try:
        from alphafold2_tpu.obs import device  # noqa: F401
    except ImportError:
        return None
    from benchmark.report import say
    t0 = time.perf_counter()
    profile = (_train_profile if run.traffic["driver"] == "train_steps"
               else _fold_profile)(run)
    say(phase="kernel_profile", seconds=time.perf_counter() - t0,
        busy_s=profile["busy_s"], unnamed_s=profile["unnamed_s"],
        events=profile["events"], kernels={
            k: v["seconds"] for k, v in profile["kernels"].items()},
        top=profile["top"])
    return profile


def read(spans, snapshot, trace, cell):
    if not trace:             # no device plane (the CPU rehearsal)
        return None
    profile = _profile(cell["run"])
    if profile is None:
        return None
    kernel = cell["metric"]["name"].split(".")[1]
    return 1e3 * profile["kernels"][kernel]["seconds"]
