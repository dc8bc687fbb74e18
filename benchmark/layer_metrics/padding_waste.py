"""Scheduler: share of the padded (batch x bucket) token grid that was
padding, over every batch the window executed. Counted by the program where
the batch is formed (`ServeMetrics.snapshot()["padding_waste"]`)."""


def read(spans, snapshot, trace, cell):
    if not snapshot.get("batches"):
        return None
    return 100.0 * snapshot["padding_waste"]
