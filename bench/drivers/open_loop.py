"""Open loop: Poisson arrivals at the mix's fixed rate, as independent
users send them.

Arrival times come from the seed.  Every `tick_ms` the generator submits
the requests that have come due as one `submit_many` burst; a collector
thread waits for the bursts in order.  Each request's latency runs from
its due time, not from when it was submitted, so a stall of the
generator shows in the latency of every request it delays; the
generator's lateness (submitted minus due) is reported beside it.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bench import serving
from bench.model import seeds


class Driver:
    def __init__(self, model, mix: dict, devices, seed: int,
                 seconds: float):
        self.model, self.mix, self.devices = model, mix, devices
        self.req = serving.Requests(model, mix, seed)
        rate = float(mix["rate_inf_per_s"])
        rng = np.random.default_rng(seeds(seed)["arrivals"])
        n = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 10)
        due = np.cumsum(rng.exponential(1.0 / rate, n))
        self.due = due[due < seconds]
        self.seconds = seconds
        self.srv = None
        self.clock, self.sleep = time.perf_counter, time.sleep

    def setup(self) -> None:
        serving.warm(self.model, self.mix, self.devices, self.req)
        self.srv = serving.started(self.model, self.mix, self.devices)

    def window(self) -> dict:
        import jax

        due, n, clock = self.due, len(self.due), self.clock
        lat = np.full(n, np.nan)
        late = np.zeros(n)
        failed = [0]
        todo: queue.SimpleQueue = queue.SimpleQueue()

        def collect():
            while (item := todo.get()) is not None:
                r0, r1, h = item
                try:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        h.wait_all(timeout=120)
                    lat[r0:r1] = clock() - t0 - due[r0:r1]
                    self.req.record(r0, h)
                except (TimeoutError, RuntimeError):
                    failed[0] += r1 - r0

        collector = threading.Thread(target=collect, name="bench-collect")
        collector.start()
        tick = self.mix["tick_ms"] * 1e-3
        model_id, srv, req = serving.MODEL_ID, self.srv, self.req
        i = 0
        t0 = clock()
        try:
            while i < n:
                now = clock() - t0
                j = n if now >= self.seconds else int(
                    np.searchsorted(due, now, side="right"))
                if j > i:
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        h = srv.submit_many(model_id, req.rows(i, j))
                    late[i:j] = clock() - t0 - due[i:j]
                    todo.put((i, j, h))
                    i = j
                wake = (np.floor(now / tick) + 1) * tick
                self.sleep(max(0.0, wake - (clock() - t0)))
        finally:
            todo.put(None)
            collector.join()
        ok = ~np.isnan(lat)
        return {"t0": t0, "seconds": self.seconds, "attempted": n,
                "failed": failed[0], "answered": int(ok.sum()),
                "latency_ms": lat[ok] * 1e3,
                "done_s": due[ok] + lat[ok], "lateness_ms": late * 1e3}

    def finish(self):
        """Drain and stop the server; returns its stats."""
        self.srv.close()
        return self.srv.stats()

    def check(self, dtype=None) -> int:
        return self.req.check(self.model, dtype)
