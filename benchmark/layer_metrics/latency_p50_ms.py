"""Scheduler: the median of the same latencies whose 95th percentile is the
cell's end-to-end metric (all requests due in the window, from when each was
due). Not an end-to-end metric itself: with the order of arrivals drawn from
the seed it spread by 5-9% over six seeds (my chip runs, PR 25), more than a
bound of 10% can admit."""


def read(spans, snapshot, trace, cell):
    return cell["end_to_end"].get("fold_latency_p50")
