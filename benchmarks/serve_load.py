"""Serving-engine load benchmark: closed- and open-loop traffic against
the PiC-BNN classification server (serve/picbnn.py).

What it answers: how much of the raw fused-pipeline batch throughput
does the serving layer keep once requests arrive one image at a time and
must be coalesced, staged, and fanned out — and what latency do clients
see as offered load approaches saturation?

  raw         — the noiseless vote spec timed at exactly max_batch (the upper
                bound: zero scheduling, zero per-request bookkeeping).
  closed loop — N client threads, each keeping a window of W requests
                outstanding (submit W, collect, repeat).  Saturates the
                engine; `sustained / raw` is the serving efficiency the
                acceptance bar cares about (>= 0.7 at saturation).
  open loop   — a pacing thread offers requests at a fixed rate
                (1 ms-tick bursts) regardless of completions, swept over
                fractions of the measured saturation throughput;
                p50/p95/p99 latency per offered-load point shows the
                hockey-stick as the queue starts to build.

The paper's 560 K inf/s silicon figure (via
`mapping.model_inference_cost` on the same 784-128-10 deployment) is
reported alongside for context — the TPU/CPU translation serves a
different regime (batched throughput vs the macro's fixed 45-cycle
pipeline), so the ratio is context, not a claim.

Results land in `BENCH_serve.json` at the repo root (schema
picbnn-bench-serve/v1) when run directly:

  PYTHONPATH=src python -m benchmarks.serve_load [--smoke] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import platform
import threading
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import bnn, ensemble, mapping
from repro.deploy import deploy
from repro.serve.picbnn import BatchingPolicy, PicBnnServer
from repro.serve.scheduler import latency_summary
from repro.spec import VOTES

REPO_ROOT = Path(__file__).resolve().parents[1]

PAPER_SIZES = (784, 128, 10)


def random_folded(sizes, seed=0, cmax=40, bias_cells=64):
    """A random deployed net with fold-style parity-adjusted C_j."""
    return bnn.random_folded(
        bnn.MLPConfig(layer_sizes=tuple(sizes), bias_cells=bias_cells),
        seed, cmax,
    )


def measure_raw(pipe, batch: int, duration_s: float, seed=1) -> dict:
    """The no-scheduler upper bound: jitted votes at exactly `batch`,
    back to back for `duration_s`.  SUSTAINED, not a rep burst: on a
    small shared host a fraction-of-a-second sample rides CPU burst
    credits and overstates what a serving loop could ever see (observed
    ~300 K inf/s for 0.3 s decaying to ~170 K sustained), so the upper
    bound is measured over the same window length as the load phases."""
    rng = np.random.default_rng(seed)
    x = rng.choice([-1.0, 1.0], (batch, PAPER_SIZES[0])).astype(np.float32)
    jax.block_until_ready(pipe.run(x, VOTES))  # compile
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < duration_s:
        jax.block_until_ready(pipe.run(x, VOTES))
        n += 1
    dt = (time.perf_counter() - t0) / n
    return {"batch": batch, "s_per_batch": dt, "inf_per_s": batch / dt,
            "duration_s": duration_s}


def _fresh_server(dep, policy: BatchingPolicy) -> PicBnnServer:
    """New engine around the SAME Deployment — its cached pipeline's jit
    programs persist, so per-phase servers add no recompiles (and
    layer_sizes for the Table-II comparison derive from the artifact)."""
    srv = PicBnnServer(policy)
    srv.register("mnist", dep)
    return srv


def closed_loop(dep, policy: BatchingPolicy, n_clients: int, window: int,
                duration_s: float, images: np.ndarray,
                depth: int = 2) -> dict:
    """Each client keeps `depth` windows of `window` requests in flight
    (submit ahead, then wait the oldest) — saturation means a backlog
    exists, and the submit-ahead keeps the dispatch thread fed so no
    stage of the pipeline ever sleeps waiting for a client wake-up."""
    srv = _fresh_server(dep, policy)
    srv.warmup()
    stop = time.perf_counter() + duration_s

    def client(ci: int):
        rng = np.random.default_rng(100 + ci)
        start = int(rng.integers(0, len(images) - window))
        burst = images[start:start + window]
        pending = [srv.submit_many("mnist", burst) for _ in range(depth)]
        while time.perf_counter() < stop:
            pending.pop(0).wait_all(timeout=120)
            pending.append(srv.submit_many("mnist", burst))
        for gh in pending:
            gh.wait_all(timeout=120)

    with srv:
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    st = srv.stats()
    ms = st.per_model["mnist"]
    return {
        "clients": n_clients,
        "window": window,
        "duration_s": duration_s,
        "n_requests": st.n_requests,
        "inf_per_s": st.inf_per_s,
        "mean_batch": st.mean_batch,
        "mean_occupancy": st.mean_occupancy,
        "queue_high_water": st.queue_high_water,
        "p50_ms": ms.latency.p50_ms,
        "p95_ms": ms.latency.p95_ms,
        "p99_ms": ms.latency.p99_ms,
        "service_p50_ms": ms.service.p50_ms,
    }


def open_loop(dep, policy: BatchingPolicy, offered_inf_per_s: float,
              duration_s: float, images: np.ndarray) -> dict:
    """Paced submission at a fixed offered rate (1 ms-tick bursts)."""
    srv = _fresh_server(dep, policy)
    srv.warmup()
    n_img = len(images)
    submitted = 0
    with srv:
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now - t0 >= duration_s:
                break
            due = int((now - t0) * offered_inf_per_s)
            if submitted < due:
                # modular gather so a post-stall catch-up burst larger
                # than the pool still submits every request it counts
                idx = np.arange(submitted, due) % n_img
                srv.submit_many("mnist", images[idx])
                submitted = due
            time.sleep(0.001)
        # close() drains everything admitted; stats cover all requests
    st = srv.stats()
    ms = st.per_model["mnist"]
    return {
        "offered_inf_per_s": offered_inf_per_s,
        "duration_s": duration_s,
        "n_requests": st.n_requests,
        "achieved_inf_per_s": st.inf_per_s,
        "mean_batch": st.mean_batch,
        "mean_occupancy": st.mean_occupancy,
        "queue_high_water": st.queue_high_water,
        "p50_ms": ms.latency.p50_ms,
        "p95_ms": ms.latency.p95_ms,
        "p99_ms": ms.latency.p99_ms,
        "queue_p99_ms": ms.queue.p99_ms,
    }


def main(fast: bool = False, json_path: str | None = None,
         write_json: bool = True):
    """fast=True is the CI smoke slice (short phases, small batches).
    write_json=False (benchmarks.run) returns rows without touching the
    committed BENCH_serve.json trajectory file."""
    import sys

    # serving is a thread pipeline (clients -> dispatch -> completion);
    # the 5 ms default GIL switch interval lets any pure-Python stage
    # convoy the others for whole batch-times.  Standard server tuning.
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(2e-4)
    try:
        return _main(fast, json_path, write_json)
    finally:
        sys.setswitchinterval(prev_switch)


def _main(fast: bool, json_path: str | None, write_json: bool):
    max_batch = 64 if fast else 256
    wait_us = 1000.0
    duration = 1.0 if fast else 4.0
    policy = BatchingPolicy(max_batch=max_batch, max_wait_us=wait_us,
                            max_inflight=4)

    folded = random_folded(PAPER_SIZES)
    # the serving deployment artifact: the server registers the SAME
    # object a checkpoint directory would reconstruct (deploy.Deployment)
    deployment = deploy(folded, ens_cfg=ensemble.EnsembleConfig(),
                        max_bucket=max_batch)
    pipe = deployment.pipeline()
    rng = np.random.default_rng(7)
    images = rng.choice([-1.0, 1.0], (1024, PAPER_SIZES[0])).astype(
        np.float32
    )

    raw_trials = [measure_raw(pipe, max_batch, duration)]
    plans = [
        mapping.plan_layer(n_out, n_in, 64)
        for n_in, n_out in zip(PAPER_SIZES[:-1], PAPER_SIZES[1:])
    ]
    silicon = mapping.model_inference_cost(
        plans, ensemble.EnsembleConfig().n_passes
    ).inferences_per_s

    print("# serve_load: section,point,inf_per_s,ratio_vs_raw,"
          "p50_ms,p95_ms,p99_ms")

    # -- closed loop: saturate, measure serving efficiency ------------
    # raw is re-measured around every load point and the MEDIAN used for
    # ratios: on a small shared host the attainable rate drifts by 2x
    # between minutes, so a single raw sample would make the efficiency
    # ratio a lottery — interleaving samples the same conditions the
    # engine ran under.
    closed = []
    points = [(1, max_batch, 3)] if fast else [(1, max_batch, 2),
                                               (1, max_batch, 3),
                                               (2, max_batch, 3)]
    for n_clients, window, depth in points:
        r = closed_loop(deployment, policy, n_clients, window, duration,
                        images, depth=depth)
        raw_trials.append(measure_raw(pipe, max_batch, duration))
        closed.append(r)
    raw = sorted(raw_trials,
                 key=lambda r: r["inf_per_s"])[len(raw_trials) // 2]
    print(f"raw,batch{raw['batch']},{raw['inf_per_s']:.0f},1.00,,,"
          f"  (median of {len(raw_trials)} interleaved trials)")
    for (n_clients, window, depth), r in zip(points, closed):
        r["depth"] = depth
        r["ratio_vs_raw"] = r["inf_per_s"] / raw["inf_per_s"]
        print(f"closed,{n_clients}x{window}d{depth},{r['inf_per_s']:.0f},"
              f"{r['ratio_vs_raw']:.3f},{r['p50_ms']:.2f},"
              f"{r['p95_ms']:.2f},{r['p99_ms']:.2f}")
    sat = max(closed, key=lambda r: r["inf_per_s"])

    # -- open loop: latency vs offered load ---------------------------
    fracs = (0.3, 0.7) if fast else (0.3, 0.6, 0.9)
    opened = []
    for frac in fracs:
        rate = frac * sat["inf_per_s"]
        r = open_loop(deployment, policy, rate, duration, images)
        r["offered_frac_of_saturation"] = frac
        opened.append(r)
        print(f"open,{frac:.1f}sat,{r['achieved_inf_per_s']:.0f},"
              f"{r['achieved_inf_per_s'] / raw['inf_per_s']:.3f},"
              f"{r['p50_ms']:.2f},{r['p95_ms']:.2f},{r['p99_ms']:.2f}")

    record = {
        "schema": "picbnn-bench-serve/v1",
        "model": {"layer_sizes": list(PAPER_SIZES),
                  "n_passes": ensemble.EnsembleConfig().n_passes},
        "policy": {"max_batch": max_batch, "max_wait_us": wait_us,
                   "max_inflight": policy.max_inflight},
        "pipeline_impl": pipe.impl,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "jax_version": jax.__version__,
        "fast": fast,
        "raw": raw,
        "raw_trials_inf_per_s": [t["inf_per_s"] for t in raw_trials],
        "silicon_equivalent_inf_per_s": silicon,
        "closed_loop": closed,
        "open_loop": opened,
        "saturation": {
            "inf_per_s": sat["inf_per_s"],
            "ratio_vs_raw": sat["ratio_vs_raw"],
            "vs_silicon_560k": sat["inf_per_s"] / silicon,
        },
    }
    print(f"# saturation: {sat['inf_per_s']:.0f} inf/s = "
          f"{sat['ratio_vs_raw']:.1%} of raw "
          f"({raw['inf_per_s']:.0f}); silicon Table-II equivalent "
          f"{silicon:.0f}")
    if write_json:
        out = Path(json_path) if json_path else REPO_ROOT / "BENCH_serve.json"
        out.write_text(json.dumps(record, indent=2) + "\n")
        print(f"# wrote {out}")
    return {"raw": raw, "closed_loop": closed, "open_loop": opened,
            "saturation": record["saturation"]}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--fast", action="store_true", dest="fast",
                    help="short CI slice (small batches, 1s phases)")
    ap.add_argument("--json", default=None, help="output path override")
    args = ap.parse_args()
    main(fast=args.fast, json_path=args.json)
