"""End-to-end inference throughput: fused packed-domain pipeline vs the
layer-by-layer unpacked baseline.

The paper's headline number is throughput (560 K inf/s) from an
end-to-end binary flow where activations never leave the array.  This
benchmark measures the TPU-translation analogue on the deployed
paper MLP (784-128-10, 33 output passes):

  baseline — the pre-pipeline deployed path: per layer, pack the ±1
             float activations (shift-broadcast pack), broadcast-XOR
             popcount matvec, +C, sign back to ±1 floats — i.e.
             activations round-trip through the unpacked domain between
             every layer — then the fused head vote.  Ops dispatch
             eagerly, exactly as `mapping.layer_forward` + `votes_fused`
             executed before the fused pipeline existed.
  fused    — `pipeline.compile_pipeline`: one compiled program, packed
             uint32 activations end to end.

Both paths are verified vote-identical before timing.  Results are
emitted as `BENCH_e2e.json` at the repo root (schema picbnn-bench-e2e/v1)
so the perf trajectory is machine-readable across PRs.

Run:  PYTHONPATH=src python -m benchmarks.e2e_throughput [--fast]
"""

from __future__ import annotations

import argparse
import json
import platform
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binarize, bnn, ensemble
from repro.deploy import deploy
from repro.spec import VOTES  # the one spec this benchmark times

REPO_ROOT = Path(__file__).resolve().parents[1]

PAPER_SIZES = (784, 128, 10)


def random_folded(sizes, seed=0, cmax=40, bias_cells=64):
    """A random deployed net with fold-style parity-adjusted C_j."""
    return bnn.random_folded(
        bnn.MLPConfig(layer_sizes=tuple(sizes), bias_cells=bias_cells),
        seed, cmax,
    )


def make_baseline(folded, head):
    """The pre-pipeline layer-by-layer unpacked deployed path (eager)."""
    w_packed = [
        binarize.pack_bits(jnp.asarray((l.weights_pm1 > 0).astype(np.uint8)))
        for l in folded[:-1]
    ]
    cs = [jnp.asarray(l.c, jnp.int32) for l in folded[:-1]]
    n_bits = [l.n_in for l in folded[:-1]]

    def baseline(x_pm1):
        h = x_pm1
        for wp, c, nb in zip(w_packed, cs, n_bits):
            # activations leave the binary domain every layer:
            # float -> bits -> packed -> int dot -> float sign
            xp = binarize.pack_bits_reference(binarize.to_bits(h))
            hd = binarize.hamming_packed(xp[:, None, :], wp)
            y = (nb - 2 * hd) + c[None, :]
            h = jnp.where(y >= 0, 1.0, -1.0)
        return ensemble.votes_fused(head, h)

    return baseline


def _time(fn, x, reps):
    jax.block_until_ready(fn(x))  # warm / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(x))
    return (time.perf_counter() - t0) / reps


def bench(sizes=PAPER_SIZES, batches=(256, 1024), reps=10, seed=0):
    folded = random_folded(sizes, seed=seed)
    ecfg = ensemble.EnsembleConfig()
    pipe = deploy(folded, ens_cfg=ecfg).pipeline()
    baseline = make_baseline(folded, pipe.head)

    rng = np.random.default_rng(seed + 1)
    results = []
    for b in batches:
        x = jnp.asarray(rng.choice([-1.0, 1.0], (b, sizes[0])), jnp.float32)
        v_fused = np.asarray(pipe.run(x, VOTES))
        v_base = np.asarray(baseline(x))
        np.testing.assert_array_equal(v_fused, v_base)  # bit-exact gate

        t_fused = _time(lambda z: pipe.run(z, VOTES), x, reps)
        t_base = _time(baseline, x, reps)
        results.append({
            "batch": int(b),
            "bit_exact": True,
            "fused_s": t_fused,
            "baseline_s": t_base,
            "fused_inf_per_s": b / t_fused,
            "baseline_inf_per_s": b / t_base,
            "speedup": t_base / t_fused,
        })
    return folded, pipe, results


def main(fast: bool = False, json_path: str | None = None, reps: int = 10,
         write_json: bool = True):
    """write_json=False (benchmarks.run) returns rows without touching
    BENCH_e2e.json — the committed trajectory file is only (re)written by
    running this module directly."""
    sizes = PAPER_SIZES
    batches = (256,) if fast else (256, 1024, 4096)
    print("# e2e throughput: batch,impl,inf_per_s,seconds_per_batch,speedup")
    folded, pipe, results = bench(
        sizes=sizes, batches=batches, reps=max(3, reps // 2) if fast else reps
    )
    for r in results:
        print(f"e2e,{r['batch']},fused-{pipe.impl},"
              f"{r['fused_inf_per_s']:.0f},{r['fused_s']:.6f},"
              f"{r['speedup']:.2f}x")
        print(f"e2e,{r['batch']},baseline-unpacked,"
              f"{r['baseline_inf_per_s']:.0f},{r['baseline_s']:.6f},1.00x")

    record = {
        "schema": "picbnn-bench-e2e/v1",
        "model": {"layer_sizes": list(sizes),
                  "n_passes": ensemble.EnsembleConfig().n_passes},
        "pipeline_impl": pipe.impl,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "jax_version": jax.__version__,
        "reps": reps,
        "results": results,
        "min_speedup": min(r["speedup"] for r in results),
        "max_speedup": max(r["speedup"] for r in results),
    }
    if write_json:
        out = Path(json_path) if json_path else REPO_ROOT / "BENCH_e2e.json"
        out.write_text(json.dumps(record, indent=2) + "\n")
        print(f"# wrote {out} (min speedup {record['min_speedup']:.2f}x)")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default=None, help="output path override")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()
    main(fast=args.fast, json_path=args.json, reps=args.reps)
