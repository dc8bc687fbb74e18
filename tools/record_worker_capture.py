#!/usr/bin/env python3
"""Records the small capture that `tests/test_obs_device.py` reduces: a few
requests of a tiny model through `serve.Scheduler` with the tracer on, under
`jax.profiler`, on the chip (send it through the chip tool):

    python tools/record_worker_capture.py chiprun_out/worker_capture

writes `<prefix>.xplane.pb.gz` (the capture without its `/host:metadata`
plane, which holds the program's HLO protos, two thirds of the bytes, and
which `jax.profiler.ProfileData` hands out nothing of) and `<prefix>.json`,
the latter holding the executable's instruction-to-`op_name` table cut to the
instructions the capture holds, and what the recorder planted: every request
is sent alone into a scheduler that holds a batch of two open for `HOLD_MS`,
so each fold is preceded by a device idle gap of that length spent in the
worker's `hold`. Copy both under `tests/data/`. Exits 2 without a TPU: the CPU backend has no
device plane to record.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LENGTH, MSA_DEPTH, REQUESTS, HOLD_MS = 8, 2, 3, 30.0


def _varint(data: bytes, i: int):
    value = shift = 0
    while True:
        byte = data[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def without_plane(xspace: bytes, name: bytes) -> bytes:
    """The serialized XSpace without the planes called `name`: a walk over
    its top-level fields (planes are field 1, length-delimited, and a
    plane's name is its field 2), everything else copied as it is."""
    out, i = bytearray(), 0
    while i < len(xspace):
        start = i
        key, i = _varint(xspace, i)
        wire = key & 7
        if wire == 2:
            size, i = _varint(xspace, i)
            body, i = xspace[i:i + size], i + size
            if key >> 3 == 1 and b"\x12" + bytes([len(name)]) + name in body:
                continue
        elif wire == 0:
            _, i = _varint(xspace, i)
        else:
            i += 8 if wire == 1 else 4
        out += xspace[start:i]
    return bytes(out)


def main(prefix: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from alphafold2_tpu import Alphafold2, serve
    from alphafold2_tpu.obs import Tracer, device

    if jax.devices()[0].platform != "tpu":
        print("record_worker_capture: needs a TPU", file=sys.stderr)
        return 2
    model = Alphafold2(dim=16, depth=1, heads=1, dim_head=8,
                       predict_coords=True, structure_module_depth=1)
    seq = jnp.zeros((1, LENGTH), jnp.int32)
    msa = jnp.zeros((1, MSA_DEPTH, LENGTH), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), seq, msa=msa)
    executor = serve.FoldExecutor(model, params)
    scheduler = serve.Scheduler(
        executor, serve.BucketPolicy((LENGTH,)),
        serve.SchedulerConfig(max_batch_size=2, max_wait_ms=HOLD_MS,
                              num_recycles=0, msa_depth=MSA_DEPTH),
        tracer=Tracer())
    scheduler.warmup()
    rng = np.random.default_rng(0)
    trace_dir = tempfile.mkdtemp(prefix="worker_capture_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with scheduler:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for _ in range(REQUESTS):
            response = scheduler.submit(serve.FoldRequest(
                seq=rng.integers(0, 20, LENGTH),
                msa=rng.integers(0, 20, (MSA_DEPTH, LENGTH)))).result(60)
            assert response.ok, response
        jax.profiler.stop_trace()
    with open(device.find_xplane(trace_dir), "rb") as f:
        xspace = without_plane(f.read(), b"/host:metadata")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(prefix)), exist_ok=True)
    with gzip.open(prefix + ".xplane.pb.gz", "wb") as f:
        f.write(xspace)

    (compiled,) = executor._cache.values()
    table = device.instruction_op_names(compiled.as_text())
    profile_data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    held = {device._instruction_of(e.name)[0]
            for _, line in device._device_lines(profile_data)
            for e in line.events}
    table = {k: v for k, v in table.items() if k in held}
    with open(prefix + ".json", "w") as f:
        json.dump({"requests": REQUESTS, "hold_ms": HOLD_MS,
                   "op_names": table}, f, indent=0)
    reduced = device.reduce(profile_data, table)
    print(json.dumps({"bytes": os.path.getsize(prefix + ".xplane.pb.gz"),
                      "table": len(table), "reduced": reduced}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(ROOT, "chiprun_out", "worker_capture")))
