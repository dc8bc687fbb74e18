"""Executor: host time around the executor per batch (`exec_busy_s` over
`batches`): host time, not a device busy share."""


def read(spans, snapshot, trace, cell):
    if not snapshot.get("batches"):
        return None
    return 1e3 * snapshot["exec_busy_s"] / snapshot["batches"]
