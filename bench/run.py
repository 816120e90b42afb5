"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload hg_mlp.offline_b2048 --seed 7 \
        --seconds 10 --trace 0

Everything is found by name from `BENCHMARK.json`: the cell names a
configuration (`bench/configs/<config>.py` and its JSON file) and a
traffic mix (`bench/traffic/<traffic>.json`), whose `driver` names
`bench/drivers/<driver>.py`.  Each metric is read by
`bench/metrics/<metric>.py`: with `--trace 0` the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics, read from a profiler
trace of the window.

A run: build the model from the seed, warm exactly the shapes the mix
uses (set-up), measure for `--seconds`, free the server, then check the
sampled answers against the plain reference (`bench/check.py`).  The
numbers compared are printed with their limits as the last lines on
standard error, and under "checks" in the result.  Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def load_module(path: Path):
    """Import a harness file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(bench: dict, name: str):
    """(cell, config entry, traffic mix) of workload `name`."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cell, config, mix


def metrics_of(bench: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics, or with `trace` its per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell["name"] in m["workloads"]]


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a metric reader sees of one run."""

    cell: dict
    mix: dict
    model: object
    device_kind: str
    chips: int
    setup_s: float
    window: dict  # the driver's host-clock record of the window
    stats: object  # the server's ServerStats, None offline
    trace: dict | None  # bench.trace.reduce output of a traced run
    driver: object  # the traffic driver, for its check


class CompileCounter:
    """Counts traces and backend compiles while it is active."""

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def chips_or_none(n: int):
    """The first `n` TPU devices, or None when there are not as many."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        return None
    return devices[:n]


def run(workload: str, seed: int, seconds: float, trace: bool,
        devices=None, mix_override: dict | None = None):
    """One run of a cell: (result dict, Context).  `devices` None looks
    for the cell's chips and raises NoChip without them; tests pass
    host devices and a `mix_override`."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, mix = cell_of(bench, workload)
    mix = {**mix, **(mix_override or {})}
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import numpy as np

    if devices is None:
        devices = chips_or_none(cell["chips"])
        if devices is None:
            raise NoChip(
                f"{workload} needs {cell['chips']} TPU chip(s); JAX found "
                f"{jax.devices()}")
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import check
    from bench import trace as tr

    config_mod = load_module(BENCH / "configs" / f"{config['name']}.py")
    model = config_mod.build(seed)
    driver = load_module(BENCH / "drivers" / f"{mix['driver']}.py").Driver(
        model, mix, devices, seed, seconds)
    driver.setup()

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    with CompileCounter() as compiles:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                window = driver.window()
        finally:
            if trace:
                jax.profiler.stop_trace()
    stats = driver.finish()
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    reduced = None
    if trace:
        try:
            ops, modules, host = tr.load(tdir)
            reduced = tr.reduce(ops, modules, host, *tr.window_of(host))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    checked = check.checks({
        "wrong_rows": driver.check(),
        "unanswered": window["failed"],
        "compiles_in_window": compiles.count,
    })
    ctx = Context(cell=cell, mix=mix, model=model,
                  device_kind=devices[0].device_kind, chips=len(devices),
                  setup_s=window["t0"] - T_START, window=window, stats=stats,
                  trace=reduced, driver=driver)
    metrics = {}
    for m in metrics_of(bench, cell, trace):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory)}
    result = {"correct": check.passed(checked),
              "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if "lateness_ms" in window:
        late = window["lateness_ms"]
        print(f"generator lateness ms: p50 {np.percentile(late, 50):.4f} "
              f"p99 {np.percentile(late, 99):.4f} max {late.max():.4f}",
              file=sys.stderr)
    result["checks"] = checked
    return result, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, _ = run(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
