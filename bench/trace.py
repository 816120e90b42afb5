"""Reduce a JAX profiler trace to device busy/idle, kernel time and idle gaps.

A TPU trace (`jax.profiler.trace`) holds one plane per chip,
`/device:TPU:<n>`, whose line "XLA Ops" has one event per executed HLO
op (its name is the op's HLO text, `%fused_mlp.1 = s32[2,8,256]{...}
custom-call(...)`), and whose line "XLA Modules" has one event per
program run (`jit__votes_off(<hash>)`).  Host planes (`/host:...`) hold
the benchmark's own spans (`bench.*`, written by
`jax.profiler.TraceAnnotation`) and the runtime's host events on each
thread.

    ops, modules, host = load(trace_dir)
    red = reduce(ops, modules, host, t0_ns, t1_ns)

`reduce` gives, over the window [t0, t1]:
  busy_s      union of op intervals per chip, averaged over chips
  idle_share  1 - busy_s / window_s
  kernels     {kernel name: [(rows, seconds), ...]} for each custom
              call (a Pallas kernel), rows read from the output shape's
              last axis, which is the batch (lane) axis of both kernels
  top_ops     the device ops that took most time, "<program>/<op>"
  idle_gaps   idle device time, each gap labelled with the host event
              that overlaps it most (the benchmark's spans included)
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
_OP = re.compile(r"%([A-Za-z_][\w\-]*?)(?:\.\d+)? = \w+\[([\d,]*)\]")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")
IDLE_HOST = "no host event"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


def load(trace_dir: str):
    """(ops, modules, host) from the one `.xplane.pb` under `trace_dir`.

    ops / modules : {device plane name: [Event]} from "XLA Ops" / "XLA
    Modules"; host : [Event] of every host line, Python frames left out.
    """
    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    ops: dict = {}
    modules: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    out = ops if line.name == OPS_LINE else modules
                    out[plane.name] = [Event(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if not e.name.startswith("$"))
    return ops, modules, host


def window_of(host) -> tuple:
    """[t0, t1] in ns of the benchmark's `bench.window` span."""
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def merged(events, t0: float, t1: float) -> list:
    """Union of the events' intervals clipped to [t0, t1], as sorted
    disjoint (start, end) pairs."""
    iv = sorted((max(e.start_ns, t0), min(e.end_ns, t1)) for e in events
                if e.end_ns > t0 and e.start_ns < t1)
    out: list = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out if e > s]


def gaps(busy, t0: float, t1: float) -> list:
    """The idle intervals of [t0, t1] around merged busy intervals."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def op_kernel(name: str):
    """(kernel name, output shape) of an HLO op event, or None."""
    m = _OP.match(name)
    if m is None:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _module_at(modules, starts, t: float) -> str:
    i = int(np.searchsorted(starts, t, side="right")) - 1
    if i >= 0 and modules[i].end_ns >= t:
        return _MODULE.match(modules[i].name).group(1)
    return "?"


def label_gaps(idle, host, window_name: str = WINDOW_SPAN) -> dict:
    """{label: seconds}: each idle gap goes to the host event overlapping
    it most (the window's own span excluded), else to IDLE_HOST."""
    cand = sorted((e for e in host if e.name != window_name),
                  key=lambda e: e.start_ns)
    starts = np.asarray([e.start_ns for e in cand], np.float64)
    ends = np.asarray([e.end_ns for e in cand], np.float64)
    # a host event longer than this is a lifetime span, not an activity
    longest = 5e7
    out: collections.Counter = collections.Counter()
    for g0, g1 in idle:
        lo = int(np.searchsorted(starts, g0 - longest))
        hi = int(np.searchsorted(starts, g1))
        if hi > lo:
            ov = np.minimum(ends[lo:hi], g1) - np.maximum(starts[lo:hi], g0)
            ov = np.where(ends[lo:hi] - starts[lo:hi] > longest, 0.0, ov)
            j = int(np.argmax(ov))
            if ov[j] > 0:
                out[cand[lo + j].name] += (g1 - g0) * 1e-9
                continue
        out[IDLE_HOST] += (g1 - g0) * 1e-9
    return dict(out)


def reduce(ops: dict, modules: dict, host: list, t0: float,
           t1: float) -> dict:
    """Device busy/idle, kernel calls, top ops and labelled idle gaps
    over the window [t0, t1] (ns); see the module docstring."""
    if not ops:
        raise ValueError("the trace holds no device plane")
    window_s = (t1 - t0) * 1e-9
    busy_s, idle = [], []
    kernels: dict = collections.defaultdict(list)
    top: collections.Counter = collections.Counter()
    for dev, events in sorted(ops.items()):
        inside = [e for e in events if e.start_ns >= t0 and e.end_ns <= t1]
        busy = merged(events, t0, t1)
        busy_s.append(sum(e - s for s, e in busy) * 1e-9)
        idle.extend(gaps(busy, t0, t1))
        mods = sorted(modules.get(dev, []), key=lambda m: m.start_ns)
        mod_starts = np.asarray([m.start_ns for m in mods], np.float64)
        for e in inside:
            parsed = op_kernel(e.name)
            short = parsed[0] if parsed else e.name.split(" ")[0]
            top[f"{_module_at(mods, mod_starts, e.start_ns)}/{short}"] += (
                (e.end_ns - e.start_ns) * 1e-9)
            if parsed and parsed[1] and " custom-call(" in e.name:
                kernels[parsed[0]].append(
                    (parsed[1][-1], (e.end_ns - e.start_ns) * 1e-9))
    busy_mean = float(np.mean(busy_s))
    gap_labels = label_gaps(idle, host)
    n_dev = len(ops)
    return {
        "devices": n_dev,
        "window_s": window_s,
        "busy_s": busy_mean,
        "idle_share": 1.0 - busy_mean / window_s,
        "kernels": dict(kernels),
        "top_ops": [[k, v / n_dev] for k, v in top.most_common(10)],
        "idle_gaps": [[k, v / n_dev] for k, v in sorted(
            gap_labels.items(), key=lambda kv: -kv[1])[:10]],
    }
