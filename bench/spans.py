"""Split the device's idle time in a traced window by what it waited on.

The program writes profiler spans (`repro.obs`): `picbnn.pack` and
`picbnn.vote` around the dispatch of its pack and vote programs, which
the device trace's "XLA Modules" line names `jit_picbnn_pack` and
`jit_picbnn_votes_*`.  `split` matches every run of those programs to
the span that enqueued it, in order, on a one-chip trace, and splits
each idle gap of the window (`bench.trace.gaps`) by the program run
that starts in it and ends it:

  host wait   the part of the gap before that run's enqueue span ended:
              the host had not handed the work over yet
  input wait  the part after it, where the run is `jit_picbnn_pack`:
              the call was issued and the device waited for its input,
              that is, for the host-to-device transfer
  rest        the rest: launch gaps before other enqueued programs,
              gaps inside a program run, and the window's tail

so that busy + host wait + input wait + rest is the window.  The clock
check counts the matched runs that start on the device before the
span that enqueued them starts, and their largest lead: the split is
sound only when there are none, or the lead is a few microseconds.

    python3 bench/spans.py --workload hg_mlp.offline_b2048 --seed 7 \
        --seconds 10

runs the cell once as `bench/run.py --trace 1` does and prints one JSON
line: the traced run's result, its rate, the split and the
`picbnn.stage` spans' mean time and bytes per row in the window.
Without a TPU it prints nothing and exits 3.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tr  # noqa: E402

PACK, VOTE = "picbnn.pack", "picbnn.vote"
PROGRAMS = {PACK: "jit_picbnn_pack", VOTE: "jit_picbnn_votes"}
STAGE = "picbnn.stage"
_RUN = re.compile(r"^(.*?)(?:\(\d+\))?$")  # "jit_picbnn_pack(123)"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    ids: dict


def load_spans(trace_dir: str) -> list:
    """The program's `picbnn.*` spans, with their stats, from the one
    `.xplane.pb` under `trace_dir`."""
    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith("picbnn.")]


def _program(module_name: str):
    """The enqueue span name of a device program run, or None."""
    name = _RUN.match(module_name).group(1)
    for span, prog in PROGRAMS.items():
        if name == prog or name.startswith(prog + "_"):
            return span
    return None


def match(spans: list, runs: list) -> list:
    """[(enqueue span, program run)] pairing each run of a program in
    PROGRAMS with its enqueue span, in order; raises when the counts of
    spans and runs of a program disagree."""
    pairs = []
    for span, prog in PROGRAMS.items():
        enq = sorted((s for s in spans if s.name == span),
                     key=lambda s: s.start_ns)
        mine = sorted((r for r in runs if _program(r.name) == span),
                      key=lambda r: r.start_ns)
        if len(enq) != len(mine):
            raise ValueError(f"{len(enq)} {span} spans but {len(mine)} "
                             f"{prog}* runs on the device")
        pairs.extend(zip(enq, mine))
    return pairs


def split(ops: dict, modules: dict, spans: list, t0: float,
          t1: float):
    """Busy, host wait, input wait and rest of the window [t0, t1] (ns),
    in seconds and as % of the window, and the clock check; None when
    the trace holds no program span (a program without `repro.obs`)."""
    if not any(s.name in PROGRAMS for s in spans):
        return None
    if len(ops) != 1:
        raise ValueError(f"the split reads one-chip traces, got {sorted(ops)}")
    (dev,) = ops
    pairs = match(spans, modules.get(dev, []))
    runs = sorted(pairs, key=lambda p: p[1].start_ns)
    starts = [r.start_ns for _, r in runs]
    busy = tr.merged(ops[dev], t0, t1)
    out = {"busy": sum(e - s for s, e in busy), "host_wait": 0.0,
           "input_wait": 0.0, "rest": 0.0}
    for g0, g1 in tr.gaps(busy, t0, t1):
        i = bisect.bisect_right(starts, g1) - 1
        if i < 0 or starts[i] < g0 or g1 >= t1:
            out["rest"] += g1 - g0  # inside a run, or the window's tail
            continue
        span, _ = runs[i]
        cut = min(max(span.end_ns, g0), g1)
        out["host_wait"] += cut - g0
        out["input_wait" if span.name == PACK else "rest"] += g1 - cut
    window = t1 - t0
    leads = [s.start_ns - r.start_ns for s, r in pairs
             if s.start_ns > r.start_ns]
    res = {f"{k}_s": v * 1e-9 for k, v in out.items()}
    res.update({f"{k}_share": 100.0 * v / window for k, v in out.items()})
    res.update(window_s=window * 1e-9, runs=len(pairs),
               runs_before_span=len(leads),
               max_lead_us=max(leads, default=0.0) * 1e-3)
    return res


def stage_stats(spans: list, t0: float, t1: float):
    """Mean duration (ms) of the `picbnn.stage` spans inside the window
    and the bytes they staged per row; None without such spans."""
    st = [s for s in spans
          if s.name == STAGE and s.start_ns >= t0 and s.end_ns <= t1]
    rows = sum(s.ids.get("rows", 0) for s in st)
    if not st or not rows:
        return None
    return {"stage_ms_per_batch":
            sum(s.end_ns - s.start_ns for s in st) / len(st) * 1e-6,
            "stage_bytes_per_inf": sum(s.ids["bytes"] for s in st) / rows,
            "stage_spans": len(st)}


def read_dir(trace_dir: str) -> dict:
    """The split and stage statistics of the trace under `trace_dir`."""
    ops, modules, host = tr.load(trace_dir)
    t0, t1 = tr.window_of(host)
    spans = load_spans(trace_dir)
    return {"split": split(ops, modules, spans, t0, t1),
            "stage": stage_stats(spans, t0, t1)}


def main(argv=None) -> int:
    from bench import run as R

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    read, load = {}, tr.load

    def load_and_read(trace_dir):  # before bench/run.py removes the trace
        tr.load = load
        read.update(read_dir(trace_dir))
        return load(trace_dir)

    tr.load = load_and_read
    try:
        result, ctx = R.run(args.workload, args.seed, args.seconds, True)
    except R.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    finally:
        tr.load = load
    w = ctx.window
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "traced_inf_per_s": w["completed_in_window"]
                      / w["seconds"], **read, "result": result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
