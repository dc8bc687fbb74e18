"""Scheduler: real rows over the rows executed (every batch is padded to
`max_batch_size`), from the program's `served` and `batches` counters."""


def read(spans, snapshot, trace, cell):
    if not snapshot.get("batches"):
        return None
    rows = snapshot["batches"] * cell["run"].traffic["max_batch_size"]
    return 100.0 * snapshot["served"] / rows
