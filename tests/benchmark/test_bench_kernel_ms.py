"""The readers of the per-layer metrics that read the program's own reducer
and worker counters (`kernel_ms.*`, `host_tail_ms.online`,
`worker_wait.online`): each `kernel_ms` entry of the manifest reads its own
kernel from one profile a run, and all of them return nothing where there is
nothing to read: no device plane, or a program without the counters."""

import pytest

import bench_tiny

from alphafold2_tpu.obs import device
from benchmark.layer_metrics import host_tail_ms, kernel_ms, worker_wait

KERNEL_ENTRIES = [m for m in bench_tiny.manifest()["per_layer"]
                  if m["name"].startswith("kernel_ms.")]
TRACE = {"busy_s": 1.0, "window_s": 2.0}      # a device plane was reduced


def test_the_manifest_lists_every_kernel_for_both_cells():
    names = {m["name"] for m in KERNEL_ENTRIES}
    assert names == {f"kernel_ms.{kernel}.{cell}"
                     for kernel in device.KERNEL_NAMES
                     for cell in ("bulk", "train")}
    assert all(m["source"] == "device_trace" and m["unit"] == "ms"
               for m in KERNEL_ENTRIES)


@pytest.mark.parametrize("metric", KERNEL_ENTRIES, ids=lambda m: m["name"])
def test_kernel_ms_reads_its_own_kernel(metric, monkeypatch):
    seconds = {k: 0.25 * (i + 1) for i, k in enumerate(device.KERNEL_NAMES)}
    calls = []

    def stub(run):
        calls.append(run)
        return {"kernels": {k: {"seconds": s, "events": 1}
                            for k, s in seconds.items()}}

    monkeypatch.setattr(kernel_ms, "_profile", stub)
    run = object()
    value = kernel_ms.read([], {}, TRACE, {"metric": metric, "run": run})
    kernel = metric["name"].split(".")[1]
    assert value == pytest.approx(1e3 * seconds[kernel])
    assert calls == [run]


def test_kernel_ms_returns_nothing_without_a_device_plane(monkeypatch):
    monkeypatch.setattr(kernel_ms, "_profile", lambda run: pytest.fail(
        "built a profile though the harness's trace had no device plane"))
    for metric in KERNEL_ENTRIES:
        assert kernel_ms.read([], {}, None,
                              {"metric": metric, "run": object()}) is None


def test_kernel_ms_returns_nothing_where_the_program_has_no_reducer(
        monkeypatch):
    monkeypatch.setattr(kernel_ms, "_profile", lambda run: None)
    assert kernel_ms.read([], {}, TRACE, {"metric": KERNEL_ENTRIES[0],
                                          "run": object()}) is None


def test_worker_counters_are_read_per_batch_and_as_a_share():
    snapshot = {"batches": 4, "fetch_s": 0.004, "resolve_s": 0.008,
                "worker_idle_s": 1.0, "worker_hold_s": 0.5,
                "worker_busy_s": 8.5, "exec_busy_s": 8.0}
    assert host_tail_ms.read([], snapshot, None, {}) == pytest.approx(3.0)
    assert worker_wait.read([], snapshot, None, {}) == pytest.approx(15.0)
    # a program without the counters (a parent commit): nothing, no raise
    old = {"batches": 4, "exec_busy_s": 8.0}
    assert host_tail_ms.read([], old, None, {}) is None
    assert worker_wait.read([], old, None, {}) is None
    assert host_tail_ms.read([], {}, None, {}) is None
