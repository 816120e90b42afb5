"""What the served drivers share: request identity, the server, the check.

Request `r` (numbered in submission order from 0) is pool row
`r % pool_rows`.  A request is checked when `r % check_every` equals an
offset drawn from the seed, so the sample is fixed by the seed.
"""

from __future__ import annotations

import threading

import numpy as np

from bench import check
from bench.model import seeds

MODEL_ID = "m"


class Requests:
    """Rows and the checked sample of a run's requests."""

    def __init__(self, model, mix: dict, seed: int):
        s = seeds(seed)
        self.pool = model.rows(np.random.default_rng(s["traffic"]),
                               mix["pool_rows"])
        self.every = int(mix["check_every"])
        self.offset = int(np.random.default_rng(s["sample"]).integers(
            self.every))
        self._lock = threading.Lock()
        self._r: list = []
        self._votes: list = []

    def rows(self, r0: int, r1: int) -> np.ndarray:
        n, p = r1 - r0, len(self.pool)
        lo = r0 % p
        if lo + n <= p:
            return self.pool[lo:lo + n]
        return self.pool[np.arange(r0, r1) % p]

    def record(self, r0: int, handle) -> None:
        """Keep the served votes of the checked requests of one burst
        (blocks until the burst is answered)."""
        n = len(handle)
        pos = np.arange((self.offset - r0) % self.every, n, self.every)
        if len(pos):
            votes = handle.votes_all()[pos]
            with self._lock:
                self._r.append(r0 + pos)
                self._votes.append(votes)

    def check(self, model, dtype=None) -> int:
        """wrong_rows of the checked requests against the reference (or,
        with `dtype`, the control's votes against it)."""
        if not self._r:
            return 0
        idx = np.concatenate(self._r) % len(self.pool)
        votes = np.concatenate(self._votes)
        uniq, inv = np.unique(idx, return_inverse=True)
        want = check.reference_votes(model, self.pool[uniq])[inv]
        if dtype is not None:
            votes = check.reference_votes(model, self.pool[uniq],
                                          dtype)[inv]
        return check.wrong_rows(votes, want)

    @property
    def n_checked(self) -> int:
        return int(sum(len(r) for r in self._r))


def server(mix: dict, devices):
    """A fresh `PicBnnServer` with the mix's batching policy and the
    server's default stats window."""
    from repro.serve.picbnn import BatchingPolicy, PicBnnServer

    return PicBnnServer(BatchingPolicy(**mix["policy"]), devices=devices)


def warm(model, mix: dict, devices, req: Requests) -> None:
    """Compile the mix's spec on its buckets and devices, and serve one
    burst per device through a throw-away server."""
    srv = server(mix, devices)
    srv.register(MODEL_ID, model.deployment)
    srv.warmup()
    n = mix["policy"]["max_batch"]
    with srv:
        hs = [srv.submit_many(MODEL_ID, req.rows(0, n)) for _ in devices]
        for h in hs:
            h.wait_all(timeout=600)


def started(model, mix: dict, devices):
    srv = server(mix, devices)
    srv.register(MODEL_ID, model.deployment)
    return srv.start()
