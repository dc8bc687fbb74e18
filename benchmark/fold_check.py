"""`correct` for the fold cells: a sample of the folds the window finished,
the longest always in it and the rest drawn from the seed, each folded again
by the plain reference at its real length, and the two compared.

Two numbers are read, each the worst over the sample:
- `coords_gap`: ||served - reference|| / ||reference|| over the CA trace;
- `confidence_gap`: max |served - reference| of the per-residue confidence.
A number is COMPARED where the traffic file gives it a limit, and printed on
an earlier line where it does not (`confidence_gap`: the fp8 control read
only 2.4 times the program's worst, under the 3 times a limit needs; PERF.md
gives the readings behind every limit).
"""

from __future__ import annotations

import functools
import time

import numpy as np


def pick_sample(done, size: int, seed: int):
    """Indices into `done` (ok folds only): the longest, then `size - 1`
    others drawn from the seed."""
    ok = [i for i, d in enumerate(done) if d[2].status == "ok"]
    if not ok:
        return []
    longest = max(ok, key=lambda i: done[i][1].length)
    others = [i for i in ok if i != longest]
    rng = np.random.default_rng([int(seed), 3])
    extra = rng.choice(len(others), size=min(size - 1, len(others)),
                       replace=False) if others else []
    return [longest] + [others[int(j)] for j in extra]


def gaps(coords, confidence, ref_coords, ref_confidence) -> dict:
    coords, ref_coords = (np.asarray(a, np.float64)
                          for a in (coords, ref_coords))
    return {"coords_gap": float(np.linalg.norm(coords - ref_coords)
                                / np.linalg.norm(ref_coords)),
            "confidence_gap": float(np.max(np.abs(
                np.asarray(confidence, np.float64)
                - np.asarray(ref_confidence, np.float64))))}


def compare_sample(run, done, kinds) -> dict:
    """{name: [worst value, limit]}; control kinds (beyond "f32") add
    `control_<kind>_<name>`, measured against the reference as the served
    folds are."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    from benchmark.report import limited, say

    cfg = run.config
    folder = {kind: jax.jit(functools.partial(
        reference.fold, cfg=cfg, num_recycles=cfg["num_recycles"], kind=kind))
        for kind in kinds}

    worst = {}
    for i in pick_sample(done, int(run.traffic["check_sample"]), run.seed):
        _, request, response, _ = done[i]
        seq, msa = jnp.asarray(request.seq), jnp.asarray(request.msa)
        t = time.perf_counter()
        ref = jax.device_get(folder["f32"](run.params, seq=seq, msa=msa))
        line = {"length": request.length,
                "reference_s": time.perf_counter() - t}
        got = gaps(response.coords, response.confidence, *ref)
        for kind in kinds[1:]:
            out = jax.device_get(folder[kind](run.params, seq=seq, msa=msa))
            got.update({f"control_{kind}_{k}": v
                        for k, v in gaps(*out, *ref).items()})
        say(phase="check_fold", **line, **got)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0.0), v)
    if not worst:                   # nothing finished: nothing is correct
        worst = {"coords_gap": 1e30}
    return limited(worst, run.traffic["limits"])
