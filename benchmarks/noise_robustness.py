"""Fig. 6-style silicon-noise robustness sweep (beyond-paper figure).

The paper's LLN argument (Sec. IV) claims the 33-pass majority vote
recovers the software logit ranking *under analog PVT noise*.  This
benchmark quantifies that claim as a robustness curve: top-1 accuracy of
the fused silicon-mode pipeline versus noise magnitude, mean ± band over
seeds, evaluated by Monte-Carlo through
the batch-draw Monte-Carlo spec (`InferenceSpec(noise="batch",
mc_samples=S)`; Hamming distances computed once, sampled thresholds
vmapped — the physics-threaded fast path).

Deployed net: a random folded paper-shape MLP; ground truth is the
full-precision logit argmax of the SAME net, so the metric isolates
exactly the paper's claim (binary vote ranking == software logit ranking)
from dataset/training effects, and the run is deterministic given seeds —
the fast slice doubles as a CI check (scripts/smoke.sh).

Also measured and recorded in BENCH_noise.json (picbnn-bench-noise/v1):
  * the fused-MC vs sequential-`votes_faithful` speedup at equal sample
    count (the slow path this pipeline replaces; acceptance bar >= 5x);
  * the LLN headline on the random net: mean SILICON logit-ranking
    recovery at 33 passes vs noiseless — a deliberately harsh metric
    (random nets have near-zero margins, so every tie counts against it);
  * `trained_lln` (full run only): the same comparison on a TRAINED
    Fig.-5 MNIST-like net — the setting the paper's "within ~1 point"
    claim is about (margins are real, the 33-pass majority absorbs the
    noise).

Run:  PYTHONPATH=src python -m benchmarks.noise_robustness [--fast]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import platform
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bnn, ensemble
from repro.core.device_model import SILICON, NoiseModel
from repro.deploy import deploy
from repro.spec import VOTES, InferenceSpec

PAPER_SIZES = (784, 128, 10)


def random_folded(sizes, seed=0, cmax=40, bias_cells=64):
    """A random deployed net with fold-style parity-adjusted C_j."""
    return bnn.random_folded(
        bnn.MLPConfig(layer_sizes=tuple(sizes), bias_cells=bias_cells),
        seed, cmax,
    )


def _mc_spec(n_mc: int) -> InferenceSpec:
    """The batch-draw Monte-Carlo request this benchmark sweeps."""
    return InferenceSpec(noise="batch", mc_samples=int(n_mc))

REPO_ROOT = Path(__file__).resolve().parents[1]


def _fp_labels(folded, x_pm1):
    """Software ground truth: full-precision logit argmax of the net."""
    h = jnp.asarray(x_pm1, jnp.float32)
    for layer in folded[:-1]:
        y = h @ jnp.asarray(layer.weights_pm1.T, jnp.float32) + jnp.asarray(
            layer.c, jnp.float32
        )
        h = jnp.where(y >= 0, 1.0, -1.0)
    out = folded[-1]
    logits = h @ jnp.asarray(out.weights_pm1.T, jnp.float32) + jnp.asarray(
        out.c, jnp.float32
    )
    return np.asarray(jnp.argmax(logits, -1)), h


def _mc_accuracy(pipe, x, labels, seeds, n_mc):
    """Mean / band of top-1 accuracy over seeds, n_mc MC draws each."""
    per_seed = []
    for s in seeds:
        votes = np.asarray(
            pipe.run(x, _mc_spec(n_mc), key=jax.random.PRNGKey(s))
        )
        per_seed.append((votes.argmax(-1) == labels[None]).mean())
    return float(np.mean(per_seed)), float(np.std(per_seed))


def bench(sizes=PAPER_SIZES, batch=512, n_mc=64, n_seeds=4,
          sigma_hd_grid=(0.0, 0.5, 1.0, 2.0, 4.0),
          drift_grid=(-8.0, -4.0, 0.0, 4.0, 8.0), seed=0):
    folded = random_folded(sizes, seed=seed)
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(rng.choice([-1.0, 1.0], (batch, sizes[0])), jnp.float32)
    labels, hidden_pm1 = _fp_labels(folded, x)
    seeds = list(range(100, 100 + n_seeds))

    pipe_nl = deploy(folded).pipeline()
    acc_noiseless = float(
        (np.asarray(pipe_nl.run(x, VOTES)).argmax(-1) == labels).mean()
    )

    rows = [("noise", "noiseless", 0.0, acc_noiseless, 0.0)]
    curves = {"sigma_hd": [], "temp_drift_hd": []}
    # accuracy vs per-row HD noise (all other sigmas off: isolate one axis)
    for s_hd in sigma_hd_grid:
        nm = NoiseModel(sigma_hd=float(s_hd), sigma_vref=0.0,
                        sigma_tjitter=0.0)
        pipe = deploy(folded, noise=nm).pipeline()
        mean, band = _mc_accuracy(pipe, x, labels, seeds, n_mc)
        curves["sigma_hd"].append(
            {"sigma_hd": float(s_hd), "top1_mean": mean, "top1_std": band}
        )
        rows.append(("noise", "sigma_hd", float(s_hd), mean, band))
    # accuracy vs systematic drift ON TOP of silicon-default randomness —
    # the TDC-competitor failure mode the paper contrasts against
    for d in drift_grid:
        nm = dataclasses.replace(SILICON, temp_drift_hd=float(d))
        pipe = deploy(folded, noise=nm).pipeline()
        mean, band = _mc_accuracy(pipe, x, labels, seeds, n_mc)
        curves["temp_drift_hd"].append(
            {"temp_drift_hd": float(d), "top1_mean": mean, "top1_std": band}
        )
        rows.append(("noise", "temp_drift_hd", float(d), mean, band))

    # --- LLN headline: full SILICON model at 33 passes vs noiseless ------
    pipe_si = deploy(folded, noise=SILICON).pipeline()
    acc_si_mean, acc_si_band = _mc_accuracy(pipe_si, x, labels, seeds, n_mc)
    rows.append(("noise", "silicon-33pass", 0.0, acc_si_mean, acc_si_band))

    # --- fused-MC vs sequential votes_faithful at equal sample count -----
    key = jax.random.PRNGKey(7)
    n_time = n_mc
    mc = _mc_spec(n_time)
    jax.block_until_ready(pipe_si.run(x, mc, key=key))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(pipe_si.run(x, mc, key=key))
    t_fused = time.perf_counter() - t0

    head = pipe_si.head
    phys = pipe_si.physics
    keys = jax.random.split(key, n_time)
    jax.block_until_ready(  # warm the eager path's caches too
        ensemble.votes_faithful(head, hidden_pm1, key=keys[0], physics=phys)
    )
    t0 = time.perf_counter()
    for k in keys:
        jax.block_until_ready(
            ensemble.votes_faithful(head, hidden_pm1, key=k, physics=phys)
        )
    t_faithful = time.perf_counter() - t0
    speedup = t_faithful / t_fused
    rows.append(("noise", "mc-speedup", float(n_time), speedup, 0.0))

    record = {
        "schema": "picbnn-bench-noise/v1",
        "model": {"layer_sizes": list(sizes), "batch": int(batch),
                  "n_passes": ensemble.EnsembleConfig().n_passes},
        "n_mc": int(n_mc),
        "n_seeds": int(n_seeds),
        "metric": "fp-logit-ranking recovery on a random net (harsh: "
                  "near-zero margins; see trained_lln for the Fig.-5 "
                  "setting)",
        "acc_noiseless": acc_noiseless,
        "acc_silicon_mean": acc_si_mean,
        "acc_silicon_std": acc_si_band,
        "ranking_delta_points": abs(acc_noiseless - acc_si_mean) * 100,
        "curves": curves,
        "speedup": {
            "n_samples": int(n_time),
            "fused_mc_s": t_fused,
            "faithful_loop_s": t_faithful,
            "speedup": speedup,
        },
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "jax_version": jax.__version__,
    }
    return rows, record


def trained_lln(n_mc=4, seed=0, epochs=6):
    """The paper's actual LLN claim: silicon vs noiseless on a TRAINED net.

    Trains the Fig.-5 synthetic-MNIST MLP and compares noiseless fused
    accuracy against the mean SILICON Monte-Carlo accuracy at the full 33
    passes.  Expected (and asserted in the slow test tier): within ~1
    point — trained margins are what the law of large numbers needs.
    """
    from repro.core import bnn
    from repro.data.synthetic import MNIST_LIKE, binarize_images, make_dataset

    cfg = bnn.MLPConfig(
        layer_sizes=(MNIST_LIKE.n_pixels, 128, MNIST_LIKE.n_classes),
        bias_cells=64,
    )
    tx, ty, vx, vy = make_dataset(
        MNIST_LIKE, n_train=6000, n_test=1500, seed=seed, noise=0.7
    )
    txb, vxb = binarize_images(tx), binarize_images(vx)
    params = bnn.train_mlp(
        jax.random.PRNGKey(seed), cfg, txb, ty, epochs=epochs, batch=128,
        lr=2e-3,
    )
    folded = bnn.fold(params, cfg)
    labels = np.asarray(vy)
    x = jnp.asarray(vxb)

    pipe_nl = deploy(folded).pipeline()
    acc_nl = float(
        (np.asarray(pipe_nl.run(x, VOTES)).argmax(-1) == labels).mean()
    )
    pipe_si = deploy(folded, noise=SILICON).pipeline()
    votes = np.asarray(
        pipe_si.run(x, _mc_spec(n_mc), key=jax.random.PRNGKey(seed + 1))
    )
    acc_si = float((votes.argmax(-1) == labels[None]).mean())
    return {
        "acc_noiseless": acc_nl,
        "acc_silicon_mean": acc_si,
        "delta_points": abs(acc_nl - acc_si) * 100,
        "n_mc": int(n_mc),
        "epochs": int(epochs),
    }


def main(fast: bool = False, write_json: bool = True,
         json_path: str | None = None):
    print("# noise robustness: section,axis,value,top1_mean,top1_band")
    t0 = time.time()
    if fast:
        rows, record = bench(batch=128, n_mc=8, n_seeds=2,
                             sigma_hd_grid=(0.0, 1.0, 2.0),
                             drift_grid=(-4.0, 0.0, 4.0))
    else:
        rows, record = bench()
        record["trained_lln"] = t = trained_lln()
        rows.append(("noise", "trained-lln-delta-points", 33.0,
                     t["delta_points"], 0.0))
        print(f"# trained LLN: noiseless {t['acc_noiseless']:.4f} vs "
              f"silicon {t['acc_silicon_mean']:.4f} "
              f"(delta {t['delta_points']:.2f} points)")
    for r in rows:
        print(f"{r[0]},{r[1]},{r[2]},{r[3]:.4f},{r[4]:.4f}")
    print(f"# ranking recovery (random net): noiseless "
          f"{record['acc_noiseless']:.4f} vs silicon "
          f"{record['acc_silicon_mean']:.4f} at 33 passes "
          f"(delta {record['ranking_delta_points']:.2f} points)")
    print(f"# fused MC vs faithful loop: "
          f"{record['speedup']['speedup']:.1f}x at "
          f"{record['speedup']['n_samples']} samples")
    print(f"# noise robustness done in {time.time() - t0:.1f}s")
    if write_json:
        out = Path(json_path) if json_path else REPO_ROOT / "BENCH_noise.json"
        out.write_text(json.dumps(record, indent=2) + "\n")
        print(f"# wrote {out}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default=None, help="output path override")
    args = ap.parse_args()
    main(fast=args.fast, json_path=args.json)
