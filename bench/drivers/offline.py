"""Offline: bulk scoring of host row batches through `Deployment.run`.

The pool holds `pool_batches` seeded batches of `batch` rows.  Each call
goes through `Deployment.run` with the noiseless spec, and the next call
is issued before the previous result is read back, so `in_flight` calls
are outstanding.  Rows read back to the host inside the window give the
rate.  Host time spent inside each `run` call (staging, packing and
program enqueue) is recorded for the `offline.host_ms_per_batch` metric.

The check compares the first result of each pool batch with the
reference, and every later result of that batch with the first.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from bench import check
from bench.model import seeds


class Driver:
    def __init__(self, model, mix: dict, devices, seed: int,
                 seconds: float):
        if len(devices) != 1:
            raise ValueError("the offline driver runs on one device")
        self.model, self.mix, self.seconds = model, mix, seconds
        rng = np.random.default_rng(seeds(seed)["traffic"])
        self.pool = [model.rows(rng, mix["batch"])
                     for _ in range(mix["pool_batches"])]
        self.first: dict = {}
        self.drift_rows = 0

    def setup(self) -> None:
        import jax

        from repro.spec import InferenceSpec

        self.spec = InferenceSpec()
        jax.block_until_ready(self.model.deployment.run(self.pool[0],
                                                        self.spec))

    def window(self) -> dict:
        import jax

        dep, spec, pool, k = (self.model.deployment, self.spec, self.pool,
                              len(self.pool))
        pending: collections.deque = collections.deque()
        host_ms, done, calls = [], 0, 0

        def read_one():
            nonlocal done
            i, out = pending.popleft()
            with jax.profiler.TraceAnnotation("bench.readback"):
                votes = np.asarray(out)
            if time.perf_counter() <= t1:
                done += len(votes)
            if i < k:
                self.first[i] = votes
            else:
                self.drift_rows += int(
                    (votes != self.first[i % k]).any(-1).sum())

        t0 = time.perf_counter()
        t1 = t0 + self.seconds
        while time.perf_counter() < t1:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.run"):
                out = dep.run(pool[calls % k], spec)
            host_ms.append((time.perf_counter() - t) * 1e3)
            pending.append((calls, out))
            calls += 1
            if len(pending) >= self.mix["in_flight"]:
                read_one()
        while pending:
            read_one()
        return {"t0": t0, "seconds": self.seconds,
                "attempted": calls * self.mix["batch"], "failed": 0,
                "answered": calls * self.mix["batch"],
                "completed_in_window": done, "host_ms": np.asarray(host_ms)}

    def finish(self):
        return None

    def check(self, dtype=None) -> int:
        wrong = 0 if dtype is not None else self.drift_rows
        for i, votes in self.first.items():
            want = check.reference_votes(self.model, self.pool[i])
            if dtype is not None:
                votes = check.reference_votes(self.model, self.pool[i],
                                              dtype)
            wrong += check.wrong_rows(votes, want)
        return wrong
