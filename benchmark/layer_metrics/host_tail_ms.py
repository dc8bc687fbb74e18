"""Scheduler: host time per batch after the device has finished, which every
row of the batch still waits through: the device-to-host fetch and the
resolve loop (`fetch_s` + `resolve_s` over `batches`). `exec_ms.online`
leaves the resolve loop out."""


def read(spans, snapshot, trace, cell):
    if not snapshot.get("batches") or "fetch_s" not in snapshot:
        return None
    return 1e3 * (snapshot["fetch_s"] + snapshot["resolve_s"]) \
        / snapshot["batches"]
