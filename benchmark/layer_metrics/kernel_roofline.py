"""Kernels: a named kernel's share of its roofline over one training step
(`kernel_roofline.<kernel>.<cell>`, %). The least time the chip could take for
what the kernel has to do, the larger of its FLOPs over the chip's bf16 peak
and its bytes over the chip's memory bandwidth (`peaks.json`), over the
kernel's own device seconds in the step's profile: the capture `kernel_ms`
makes after the window and shares (`obs.device.profile` of the jitted step,
one execution). The FLOPs and bytes are the family's count from the shapes
(`kernel_costs`: forward once and backward twice, the causal half of the
scores, the expected routed rows, nothing made again, no padded row), while
the seconds hold everything the kernel ran, what it made again for the
backward too: the share cannot pass 100% and recomputation lowers it."""

from benchmark.layer_metrics import kernel_ms


def read(spans, snapshot, trace, cell):
    run = cell["run"]
    costs_of = getattr(run.family, "kernel_costs", None)
    if not trace or costs_of is None:
        return None
    profile = kernel_ms._profile(run)
    if profile is None:
        return None
    kernel = cell["metric"]["name"].split(".")[1]
    seconds = profile["kernels"].get(kernel, {}).get("seconds")
    if not seconds:
        return None
    flops, moved = costs_of(run.config, run.traffic)[kernel]
    peaks = run.peaks[run.devices[0].device_kind]
    floor = max(flops / peaks["bf16_flops_per_s"],
                moved / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
