"""Expert layers: the share of the held experts' buffers that routed slots
fill (`expert_fill.<cell>`, %): `expert_slots`, the slots a step routes to
the experts held here (the mean over the expert layers), over the STATIC rows
of one layer's buffer, every one of which the step computes. The rest is the
price of a step whose device time does not follow the routing.

The driver's window keeps the loss alone, so the reader makes one call of its
own after the window: one forward pass on the seed's first batch, through the
family (`expert_counters`), on the benchmark's own weights. A slot beyond the
buffer (`expert_overflow`) would have failed the step in the window; it is
printed here on an earlier line beside the fullest expert's load."""

import functools


@functools.lru_cache(maxsize=1)
def _counters(run):
    from benchmark.report import say
    counters = run.family.expert_counters(run)
    say(phase="expert_counters", **counters)
    return counters


def read(spans, snapshot, trace, cell):
    run = cell["run"]
    if not hasattr(run.family, "expert_counters"):
        return None
    counters = _counters(run)
    return 100.0 * counters["expert_slots"] / counters["expert_rows"]
