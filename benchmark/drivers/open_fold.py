"""Open loop of folds through `serve.Scheduler.submit`: arrivals on a
schedule fixed by the traffic file (an exponential's quantile gaps at a fixed
rate, shuffled by the seed), sent whether or not earlier ones have come back.

Every request is timed from when it was DUE, so a stall counts against all
that it delays; how late the sender itself ran is reported beside the
result. A request that is shed, rejected, failed or still unanswered a minute
after the window counts with the worst latency. The window is `--seconds` of
arrivals; the run then waits for what is still in flight.
"""

from __future__ import annotations

import math
import threading
import time

from benchmark import fold_check, traffic_gen

GRACE_S = 60.0          # how long past the window an answer is waited for


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1,
                       max(0, math.ceil(q / 100.0 * len(ordered)) - 1))]


class Driver:
    def __init__(self, run):
        from alphafold2_tpu import serve
        from alphafold2_tpu.obs.trace import Tracer
        self.run, t = run, run.traffic
        self.executor = serve.FoldExecutor(run.model, run.params,
                                           max_entries=2 * len(t["buckets"]))
        # the program's own spans are read in traced runs only
        self.tracer = Tracer(slow_k=1_000_000) if run.trace else None
        self.scheduler = serve.Scheduler(
            self.executor, serve.BucketPolicy(tuple(t["buckets"])),
            serve.SchedulerConfig(
                max_batch_size=t["max_batch_size"],
                max_wait_ms=t["max_wait_ms"],
                num_recycles=run.config["num_recycles"],
                msa_depth=run.config["msa_depth"],
                queue_limit=t["queue_limit"]),
            tracer=self.tracer)
        self.schedule = traffic_gen.open_loop_schedule(run.seed, t,
                                                       run.seconds)
        rng = traffic_gen._rng(run.seed, 4)
        self.requests = [traffic_gen.fold_request(
            rng, length, run.config["msa_depth"])
            for _, length in self.schedule]
        self.done = []          # (index, request, response, t_done)

    def warm(self):
        self.scheduler.warmup()
        self.scheduler.start()

    def window(self) -> dict:
        from alphafold2_tpu.serve.scheduler import QueueFullError
        run = self.run
        lock, landed = threading.Lock(), {}
        late, refused = [], 0
        t0 = time.perf_counter()
        for index, ((due, _), request) in enumerate(
                zip(self.schedule, self.requests)):
            while True:
                now = time.perf_counter() - t0
                run.tick(now)
                if now >= due:
                    break
                with run.annotate("wait"):
                    time.sleep(min(due - now, 0.02))
            late.append(time.perf_counter() - t0 - due)
            try:
                with run.annotate("submit"):
                    ticket = self.scheduler.submit(request)
            except QueueFullError:
                refused += 1
                continue

            def on_done(resp, index=index, request=request):
                with lock:
                    landed[index] = (index, request, resp,
                                     time.perf_counter())
            ticket.add_done_callback(on_done)
        while time.perf_counter() - t0 < run.seconds:
            run.tick(time.perf_counter() - t0)
            time.sleep(0.02)
        in_flight_at_close = len(self.schedule) - refused - len(landed)
        deadline = t0 + run.seconds + GRACE_S
        while len(landed) + refused < len(self.schedule) \
                and time.perf_counter() < deadline:
            time.sleep(0.02)
        drained_s = time.perf_counter() - t0 - run.seconds
        with lock:
            self.done = [landed[i] for i in sorted(landed)]
        worst = run.seconds + GRACE_S
        latencies = [worst] * len(self.schedule)
        for index, _, resp, t_done in self.done:
            if resp.status == "ok":
                latencies[index] = t_done - t0 - self.schedule[index][0]
        ok = sum(d[2].status == "ok" for d in self.done)
        spans = []
        if self.tracer is not None:
            due_to_submit = {r.request_id: lt
                             for r, lt in zip(self.requests, late)}
            spans = [dict(rec, due_to_submit_s=due_to_submit.get(
                rec["request_id"], 0.0)) for rec
                in self.tracer.slowest()]
        return {"window_s": run.seconds, "attempted": len(self.schedule),
                "failed": len(self.schedule) - ok,
                "snapshot": self.scheduler.metrics.snapshot(),
                "spans": spans,
                "folds": [(req.length, resp.bucket_len)
                          for _, req, resp, _ in self.done
                          if resp.status == "ok"],
                "end_to_end": {
                    "fold_latency_p50": 1e3 * percentile(latencies, 50),
                    "fold_latency_p95": 1e3 * percentile(latencies, 95)},
                "notes": {"requests": len(self.schedule),
                          "refused": refused,
                          "generator_late_ms_mean":
                              1e3 * sum(late) / max(len(late), 1),
                          "generator_late_ms_max": 1e3 * max(late, default=0),
                          "in_flight_at_close": in_flight_at_close,
                          "drained_after_s": drained_s,
                          "latency_max_ms": 1e3 * max(latencies)}}

    def release(self):
        self.scheduler.stop(drain=False)
        self.scheduler = self.executor = None

    def check(self, kinds) -> dict:
        return fold_check.compare_sample(self.run, self.done, kinds)


from benchmark.drivers.closed_fold import (largest_program,  # noqa: E402,F401
                                           reference_program)
