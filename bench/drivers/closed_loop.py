"""Closed loop: clients that each keep a fixed number of bursts waiting.

Each of `clients` threads submits `outstanding` bursts of `burst` rows,
then, each time its oldest burst is answered, submits the next one, so
the server holds clients x outstanding bursts at all times.  Requests
completed inside the window, counted on the client side, give the rate.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time

from bench import serving


class Driver:
    def __init__(self, model, mix: dict, devices, seed: int,
                 seconds: float):
        self.model, self.mix, self.devices = model, mix, devices
        self.req = serving.Requests(model, mix, seed)
        self.seconds = seconds
        self.srv = None
        self.most_pending = [0] * mix["clients"]  # per client

    def setup(self) -> None:
        serving.warm(self.model, self.mix, self.devices, self.req)
        self.srv = serving.started(self.model, self.mix, self.devices)

    def window(self) -> dict:
        import jax

        mix, req, srv = self.mix, self.req, self.srv
        b = int(mix["burst"])
        bursts = itertools.count()
        done = [0] * mix["clients"]
        sent = [0] * mix["clients"]
        failed = [0] * mix["clients"]

        def submit(ci, pending):
            r0 = next(bursts) * b
            with jax.profiler.TraceAnnotation("bench.submit"):
                h = srv.submit_many(serving.MODEL_ID, req.rows(r0, r0 + b))
            pending.append((r0, h))
            sent[ci] += b
            self.most_pending[ci] = max(self.most_pending[ci], len(pending))

        def client(ci):
            pending: collections.deque = collections.deque()
            for _ in range(mix["outstanding"]):
                submit(ci, pending)
            while pending:
                r0, h = pending.popleft()
                try:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        h.wait_all(timeout=120)
                    if time.perf_counter() <= t1:
                        done[ci] += b
                    req.record(r0, h)
                except (TimeoutError, RuntimeError):
                    failed[ci] += b
                if time.perf_counter() < t1:
                    submit(ci, pending)

        threads = [threading.Thread(target=client, args=(ci,),
                                    name=f"bench-client-{ci}")
                   for ci in range(mix["clients"])]
        t0 = time.perf_counter()
        t1 = t0 + self.seconds
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {"t0": t0, "seconds": self.seconds, "attempted": sum(sent),
                "failed": sum(failed), "answered": sum(sent) - sum(failed),
                "completed_in_window": sum(done)}

    def finish(self):
        self.srv.close()
        return self.srv.stats()

    def check(self, dtype=None) -> int:
        return self.req.check(self.model, dtype)
