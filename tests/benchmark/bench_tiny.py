"""A directory of tiny cells for the CPU rehearsals: the committed
BENCHMARK.json, configurations and traffic files, with sizes cut so that one
run takes seconds on the CPU. Nothing here is a measurement."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_MODEL = dict(dim=32, depth=2, heads=2, dim_head=16, msa_depth=4,
                  num_recycles=1, dtype="float32")
TINY_TRAFFIC = {
    "closed_fold": dict(buckets=[16, 24], lengths=[10, 12, 16, 20, 24],
                        outstanding=3, trace_seconds=1),
    "train_steps": dict(crop=16, trace_seconds=1),
    "open_fold": dict(buckets=[16, 24], length_mix=[[16, 0.5], [24, 0.5]],
                      rate_per_s=20.0, block=10, length_steps=2, max_batch_size=2,
                      trace_seconds=1),
}
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12}}


def tiny_root(tmp_path) -> str:
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for sub, patch in (("configs", lambda d: TINY_MODEL),
                       ("traffic", lambda d: TINY_TRAFFIC[d["driver"]])):
        os.makedirs(os.path.join(root, "benchmark", sub))
        src = os.path.join(REPO, "benchmark", sub)
        for name in os.listdir(src):
            with open(os.path.join(src, name)) as f:
                data = json.load(f)
            data.update(patch(data))
            with open(os.path.join(root, "benchmark", sub, name), "w") as f:
                json.dump(data, f)
    return root


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_tiny(tmp_path, monkeypatch, capsys, workload, trace, seed=3000000019,
             seconds=1.0):
    """One in-process run of benchmark/run.py on the CPU's first device;
    returns (exit code, the last line of standard output as a dict)."""
    import jax
    from benchmark import run as brun
    monkeypatch.setattr(brun, "load_peaks", lambda: CPU_PEAKS)
    rc = brun.main(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)],
                   devices=jax.devices()[:1], root=tiny_root(tmp_path))
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1])
