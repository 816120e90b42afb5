"""The drivers' accounting on the host, with fake servers and no timing."""

import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from bench import serving  # noqa: E402
from bench.drivers import closed_loop, open_loop  # noqa: E402


class FakeModel:
    n_classes = 3

    @staticmethod
    def rows(rng, n):
        return rng.random((n, 4)).astype(np.float32)


class FakeHandle:
    def __init__(self, n, wait=None):
        self.n, self._wait = n, wait

    def __len__(self):
        return self.n

    def wait_all(self, timeout=None):
        if self._wait:
            self._wait()
        return np.zeros(self.n, np.int64)

    def votes_all(self, timeout=None):
        return np.zeros((self.n, 3), np.int32)


class FakeClock:
    """Time moves only when the generator sleeps or a stall is injected."""

    def __init__(self):
        self.t = 100.0
        self.lock = threading.Lock()

    def __call__(self):
        with self.lock:
            return self.t

    def advance(self, dt):
        with self.lock:
            self.t += dt


MIX = {"pool_rows": 64, "check_every": 8, "tick_ms": 1,
       "rate_inf_per_s": 2000, "policy": {"max_batch": 256}}


def test_open_loop_latency_runs_from_due_time_and_shows_a_stall():
    clock = FakeClock()
    drv = open_loop.Driver(FakeModel(), MIX, [None], seed=3, seconds=0.5)
    drv.clock = clock

    def sleep(dt):
        time.sleep(2e-4)  # lets the collector keep up in real time
        clock.advance(max(dt, 1e-4))

    drv.sleep = sleep
    calls = [0]

    class StallingServer:
        def submit_many(self, model_id, rows):
            calls[0] += 1
            if calls[0] == 100:  # the generator stalls for 40 ms
                clock.advance(0.040)
            return FakeHandle(len(rows))

    drv.srv = StallingServer()
    w = drv.window()
    assert w["attempted"] == len(drv.due) > 500
    assert w["failed"] == 0
    lat, late = w["latency_ms"], w["lateness_ms"]
    assert len(lat) == w["attempted"]
    # the server answers at once, so only the generator delays a
    # request: the stall makes it late by up to 40 ms, and the latency,
    # timed from the due time, carries that wait
    assert 39.0 < late.max() < 42.0
    assert (lat >= late - 1e-9).all()
    assert (late > 5.0).sum() > 40  # about 2000/s x 35 ms requests
    assert (lat > 5.0).sum() >= (late > 5.0).sum()


def test_closed_loop_holds_its_outstanding_bursts():
    mix = {**MIX, "clients": 3, "outstanding": 4, "burst": 16}
    drv = closed_loop.Driver(FakeModel(), mix, [None], seed=5,
                             seconds=0.3)
    pending = [0]
    most = [0]
    lock = threading.Lock()

    class Server:
        def submit_many(self, model_id, rows):
            with lock:
                pending[0] += 1
                most[0] = max(most[0], pending[0])

            def wait():
                with lock:
                    pending[0] -= 1

            return FakeHandle(len(rows), wait)

    drv.srv = Server()
    w = drv.window()
    assert drv.most_pending == [4, 4, 4]
    assert most[0] <= 3 * 4
    assert w["attempted"] % 16 == 0 and w["failed"] == 0
    assert w["completed_in_window"] <= w["attempted"]


def test_requests_sample_is_fixed_by_the_seed():
    a = serving.Requests(FakeModel(), MIX, seed=2 ** 40 + 1)
    b = serving.Requests(FakeModel(), MIX, seed=2 ** 40 + 1)
    c = serving.Requests(FakeModel(), MIX, seed=2 ** 40 + 2)
    assert np.array_equal(a.pool, b.pool) and a.offset == b.offset
    assert not np.array_equal(a.pool, c.pool)
    # rows wrap around the pool
    assert np.array_equal(a.rows(60, 70), a.pool[np.arange(60, 70) % 64])
