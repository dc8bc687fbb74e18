"""Scheduler: share of the worker thread's time in service spent waiting
(`worker_idle_s`, parked with nothing pending, + `worker_hold_s`, entries
pending and no batch ready yet) over waiting + `worker_busy_s`. Read beside
`device_idle.online`: where the worker waits and the device does not idle, or
the reverse, one of the two clocks is wrong."""


def read(spans, snapshot, trace, cell):
    if "worker_busy_s" not in snapshot:
        return None
    waiting = snapshot["worker_idle_s"] + snapshot["worker_hold_s"]
    total = waiting + snapshot["worker_busy_s"]
    return 100.0 * waiting / total if total else None
