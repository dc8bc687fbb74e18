"""Closed loop of folds through `serve.Scheduler.submit`: `outstanding`
requests in flight at all times, the next one sent when one comes back.

The traffic file fixes a multiset of lengths; the seed shuffles each cycle
of it and draws every sequence and MSA. Every window therefore holds the same
mix of work whatever the seed: it closes at the first completed cycle once
`--seconds` have passed, and the rate is all folds over all of that time.
"""

from __future__ import annotations

import queue
import time

from benchmark import fold_check, traffic_gen


class Driver:
    def __init__(self, run):
        from alphafold2_tpu import serve
        self.run, t = run, run.traffic
        self.executor = serve.FoldExecutor(run.model, run.params,
                                           max_entries=2 * len(t["buckets"]))
        self.scheduler = serve.Scheduler(
            self.executor, serve.BucketPolicy(tuple(t["buckets"])),
            serve.SchedulerConfig(
                max_batch_size=t["max_batch_size"],
                num_recycles=run.config["num_recycles"],
                msa_depth=run.config["msa_depth"],
                queue_limit=4 * t["outstanding"]))
        self.requests = traffic_gen.closed_loop_requests(
            run.seed, t["lengths"], run.config["msa_depth"])
        self.done = []          # (index, request, response, t_done)

    def warm(self):
        """Each bucket's program once (compile, or read from the cache, and
        one execution of a zero batch), then the scheduler's thread."""
        self.scheduler.warmup()
        self.scheduler.start()

    def window(self) -> dict:
        run, t = self.run, self.run.traffic
        cycle, landed = len(t["lengths"]), queue.Queue()
        sent = 0

        def send():
            nonlocal sent
            index, request = sent, next(self.requests)
            with run.annotate("submit"):
                ticket = self.scheduler.submit(request)
            ticket.add_done_callback(
                lambda resp: landed.put(
                    (index, request, resp, time.perf_counter())))
            sent += 1

        t0 = time.perf_counter()
        for _ in range(t["outstanding"]):
            send()
        while True:
            run.tick(time.perf_counter() - t0)
            try:
                with run.annotate("wait"):
                    item = landed.get(timeout=0.05)
            except queue.Empty:
                continue
            self.done.append(item)
            if item[3] - t0 >= run.seconds and len(self.done) % cycle == 0:
                break
            send()
        window_s = self.done[-1][3] - t0
        snapshot = self.scheduler.metrics.snapshot()
        ok = [d for d in self.done if d[2].status == "ok"]
        return {"window_s": window_s, "attempted": len(self.done),
                "failed": len(self.done) - len(ok), "snapshot": snapshot,
                "folds": [(req.length, resp.bucket_len) for _, req, resp, _
                          in ok],
                "end_to_end": {
                    "fold_throughput": len(ok) / window_s * 3600.0},
                "notes": {"cycles": len(self.done) // cycle,
                          "statuses": sorted({d[2].status
                                              for d in self.done})}}

    def release(self):
        """Stops the scheduler without folding what is still queued and
        drops the program's compiled state; the weights stay (they are the
        benchmark's, and the reference reads them)."""
        self.scheduler.stop(drain=False)
        self.scheduler = self.executor = None

    def check(self, kinds) -> dict:
        return fold_check.compare_sample(self.run, self.done, kinds)


def largest_program(model, param_shapes, config, traffic, place):
    """For `reckon_bytes.py`: the fold program of the longest bucket."""
    import jax
    import jax.numpy as jnp
    from alphafold2_tpu import predict
    b, n, m = traffic["max_batch_size"], max(traffic["buckets"]), \
        config["msa_depth"]
    fold = jax.jit(lambda p, seq, msa, mask, msa_mask: predict.fold(
        model, p, seq, msa=msa, mask=mask, msa_mask=msa_mask,
        num_recycles=config["num_recycles"]))
    return (f"fold b={b} bucket={n} msa={m}", fold,
            (param_shapes, place((b, n), jnp.int32),
             place((b, m, n), jnp.int32), place((b, n), bool),
             place((b, m, n), bool)))


def reference_program(param_shapes, config, traffic, place):
    """For `reckon_bytes.py --reference`: the plain reference at the longest
    request the check can draw."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    n = max(traffic.get("lengths") or
            [edge for edge, _ in traffic["length_mix"]])
    fold = jax.jit(lambda p, seq, msa: reference.fold(
        p, config, seq, msa, config["num_recycles"]))
    return (f"f32 reference fold at length {n}", fold,
            (param_shapes, place((n,), jnp.int32),
             place((config["msa_depth"], n), jnp.int32)))
