"""Scheduler: mean time from when a request was due to when the batch it
rode in began to form (the program's own `batch_form` span, read from its
tracer in traced runs, plus how late the generator sent it)."""


def read(spans, snapshot, trace, cell):
    waits = []
    for record in spans:
        formed = [s["start_s"] for s in record.get("spans", [])
                  if s["name"] == "batch_form"]
        if formed:
            waits.append(record["due_to_submit_s"] + min(formed))
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
