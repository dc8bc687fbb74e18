"""CPU rehearsal of `benchmark/run.py` at a tiny size, one per driver and per
`--trace` mode: the last line has the contract's keys, names the cell's own
metrics, and `correct` is true against the plain reference."""

import pytest

import bench_tiny

CELLS = [c["name"] for c in bench_tiny.manifest()["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_the_contracts_line(workload, trace, tmp_path,
                                             monkeypatch, capsys):
    rc, result = bench_tiny.run_tiny(tmp_path, monkeypatch, capsys, workload,
                                     trace)
    assert rc == 0
    assert RESULT_KEYS <= set(result)
    assert set(result) - RESULT_KEYS <= {"breakdown", "compared"}
    assert list(result)[-1] == "compared"        # the compared numbers last
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(result["device"])

    spec = bench_tiny.manifest()
    in_cell = lambda m: workload in m.get("workloads", [workload])
    if trace:
        allowed = {m["name"] for m in spec["per_layer"] if in_cell(m)}
        # the CPU has no device plane: readers of the trace return nothing
        assert set(result["metrics"]) <= allowed
        assert set(result["metrics"]) >= {
            m["name"] for m in spec["per_layer"]
            if in_cell(m) and m["source"] != "device_trace"}
    else:
        assert set(result["metrics"]) == {
            m["name"] for m in spec["end_to_end"] if in_cell(m)}
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    for name, metric in result["metrics"].items():
        assert metric["unit"] == units[name]
        assert metric["value"] > 0
    for name, (value, limit) in result["compared"].items():
        assert value <= limit, name


def test_same_seed_same_inputs(tmp_path):
    import numpy as np
    from benchmark import traffic_gen
    a = traffic_gen.closed_loop_requests(2 ** 31 + 5, [10, 12, 16], 4)
    b = traffic_gen.closed_loop_requests(2 ** 31 + 5, [10, 12, 16], 4)
    for _ in range(7):
        ra, rb = next(a), next(b)
        assert np.array_equal(ra.seq, rb.seq)
        assert np.array_equal(ra.msa, rb.msa)
