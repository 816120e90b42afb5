"""Program spans and counters, on the profiler's clock.

    with obs.span("picbnn.run", call=7):     # a profiler trace event
        xd = obs.stage(x)                    # device_put, counted
    before = obs.counters()
    ...
    after = obs.counters()                   # read deltas of two snapshots

`span(name, **ids)` is a `jax.profiler.TraceAnnotation`: while a profiler
runs it lands in the same trace as the device's ops, on the same clock,
with each id (`call=`, `batch=`) as an event stat.  With no profiler
running it costs the inactive-trace check alone.  There is no switch.

Counters are process-wide running totals that never reset; a reader
takes the difference of two `counters()` snapshots.  The program counts
what it stages to the device (`stage.bytes`, `stage.rows`,
`stage.calls`, `stage.ns`) and, once `watch_gc()` has run, the
interpreter's collector (`gc.collections`, `gc.pause_ns`).
"""

from __future__ import annotations

import gc
import threading
import time

import jax
import numpy as np

_lock = threading.Lock()
_counts: dict = {}
# the collector's totals, written only by its callback: collections are
# serialized, and the callback must not take `_lock`, which the thread
# that triggered the collection may hold
_gc = {"watched": False, "span": None, "t0": 0, "collections": 0,
       "pause_ns": 0}


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A profiler trace span named `name`, with `ids` as its stats."""
    return jax.profiler.TraceAnnotation(name, **ids)


def count(name: str, n: int = 1) -> None:
    """Add `n` to counter `name`."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def counters() -> dict:
    """A snapshot of every counter."""
    with _lock:
        out = dict(_counts)
    if _gc["watched"]:
        out["gc.collections"] = _gc["collections"]
        out["gc.pause_ns"] = _gc["pause_ns"]
    return out


def stage(x, target=None, *, rows: int | None = None, **ids):
    """Put host array `x` on `target` (a device or sharding; None: the
    default device) inside span `picbnn.stage`, counting its bytes and
    `rows` (default: its leading dimension).  A `jax.Array` is already
    staged: it passes through and counts nothing."""
    if isinstance(x, jax.Array):
        return x
    x = np.asarray(x)
    nbytes = x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize
    if rows is None:
        rows = x.shape[0] if x.ndim else 1
    t0 = time.perf_counter_ns()
    with span("picbnn.stage", bytes=nbytes, rows=rows, **ids):
        out = jax.device_put(x, target)
    dt = time.perf_counter_ns() - t0
    with _lock:
        for k, n in (("stage.bytes", nbytes), ("stage.rows", rows),
                     ("stage.calls", 1), ("stage.ns", dt)):
            _counts[k] = _counts.get(k, 0) + n
    return out


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc["t0"] = time.perf_counter_ns()
        _gc["span"] = span("py.gc", generation=info["generation"])
        _gc["span"].__enter__()
    elif _gc["span"] is not None:
        _gc["span"].__exit__(None, None, None)
        _gc["span"] = None
        _gc["collections"] += 1
        _gc["pause_ns"] += time.perf_counter_ns() - _gc["t0"]


def watch_gc() -> None:
    """Record each collector pass as a `py.gc` span and in the
    `gc.collections` / `gc.pause_ns` counters (idempotent)."""
    with _lock:
        if _gc["watched"]:
            return
        _gc["watched"] = True
    gc.callbacks.append(_on_gc)
