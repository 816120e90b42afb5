"""Fig. 5 reproduction: TOP-1/TOP-2 accuracy vs output-layer executions.

The paper sweeps the number of fully-connected output-layer executions
(1..33, HD thresholds {0,2,...,64}) and reports MNIST / Hand-Gesture
accuracy converging to (near) the software baseline.  We reproduce the
sweep on synthetic drop-in datasets under three conditions:
  * noiseless compare (TPU semantics / fused kernel),
  * silicon-like PVT noise — the fused physics-threaded pipeline
    (`compile_pipeline(..., noise=SILICON)`), Monte-Carlo over seeds via
    the cumulative batch-draw spec at fused speed (the sequential `votes_faithful` loop this
    replaces is timed against it in benchmarks/noise_robustness.py),
  * the hierarchical (strictly binary) input-layer mode.

Output: CSV rows  dataset,mode,n_passes,top1,top2
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import pipeline
from repro.core import bnn, ensemble, mapping
from repro.core.device_model import SILICON
from repro.deploy import deploy
from repro.spec import VOTES, InferenceSpec
from repro.data.synthetic import (
    HG_LIKE,
    MNIST_LIKE,
    binarize_images,
    make_dataset,
)

#: the silicon truncated-sweep request this benchmark Monte-Carlos
CUM_SILICON = InferenceSpec(noise="batch", cumulative=True)


def _sweep_noiseless_fused(pipe: "pipeline.CompiledPipeline", votes, n_passes):
    """Guarded `sweep_from_votes`: valid ONLY for a noiseless pipeline.

    The staircase reconstruction breaks under sampled thresholds (see
    ensemble.sweep_from_votes / DESIGN.md §8); silicon-mode sweeps must go
    through the cumulative spec (`InferenceSpec(noise="batch",
    cumulative=True)`) instead.
    """
    assert pipe.physics is None or pipe.physics.is_noiseless, (
        "sweep_from_votes is noiseless-only; run the cumulative silicon "
        "spec for silicon-mode truncated sweeps"
    )
    return ensemble.sweep_from_votes(votes, n_passes)


def run_dataset(name: str, spec, hidden: int, epochs: int, seed: int = 0,
                noise: float = 0.7):
    """noise=0.7 calibrates the synthetic MNIST-like task so the fp32
    software baseline lands at ~95% — the paper's MNIST operating point —
    making the binary-vs-baseline gap comparable to Fig. 5."""
    cfg = bnn.MLPConfig(
        layer_sizes=(spec.n_pixels, hidden, spec.n_classes), bias_cells=64
    )
    tx, ty, vx, vy = make_dataset(
        spec, n_train=6000, n_test=1500, seed=seed, noise=noise
    )
    txb, vxb = binarize_images(tx), binarize_images(vx)
    params = bnn.train_mlp(
        jax.random.PRNGKey(seed), cfg, txb, ty, epochs=epochs, batch=128,
        lr=2e-3,
    )
    sw = bnn.eval_accuracy(params, cfg, vxb, vy, topk=(1, 2))
    rows = [
        (name, "software-fp-logits", 0, sw["top1"], sw["top2"]),
    ]

    folded = bnn.fold(params, cfg)
    mapped = [mapping.map_layer(l, cfg.bias_cells) for l in folded[:-1]]

    # noiseless: ONE fused end-to-end packed-domain pipeline pass; the
    # whole truncated-threshold sweep is recovered from the fused vote
    # totals (ensemble.sweep_from_votes, noiseless-only — guarded)
    # instead of 33 re-searches.
    ecfg = ensemble.EnsembleConfig()
    pipe = deploy(folded, ens_cfg=ecfg).pipeline()
    votes = pipe.run(jnp.asarray(vxb), VOTES)
    cum = _sweep_noiseless_fused(pipe, votes, ecfg.n_passes)
    sweep = ensemble.accuracy_from_cumulative(cum, vy)
    for p in (1, 3, 5, 9, 17, 25, 33):
        rows.append((name, "noiseless", p, sweep[p]["top1"], sweep[p]["top2"]))

    # silicon PVT noise: the SAME fused pipeline with the device physics
    # threaded through (sampled per-pass thresholds), Monte-Carlo over
    # seeds — per-pass trajectories via the cumulative spec at fused speed.
    n_mc = 2 if epochs <= 3 else 4
    pipe_si = deploy(folded, ens_cfg=ecfg, noise=SILICON).pipeline()
    acc = {}
    for i in range(n_mc):
        cum = pipe_si.run(jnp.asarray(vxb), CUM_SILICON,
                          key=jax.random.PRNGKey(seed + 1 + i))
        s = ensemble.accuracy_from_cumulative(cum, vy)
        for p, d in s.items():
            for k, v in d.items():
                acc.setdefault(p, {}).setdefault(k, []).append(v)
    for p in (1, 3, 5, 9, 17, 25, 33):
        rows.append((name, "silicon-noise", p,
                     float(np.mean(acc[p]["top1"])),
                     float(np.mean(acc[p]["top2"]))))

    # strictly-binary hierarchical mode keeps the faithful CAM-tile flow
    h = jnp.asarray(vxb)
    for ml in mapped:
        h = mapping.layer_forward(ml, h, "hierarchical")
    head = ensemble.build_head(folded[-1], ecfg)
    sweep = ensemble.accuracy_sweep(head, h, jnp.asarray(vy), ecfg)
    for p in (1, 3, 5, 9, 17, 25, 33):
        rows.append(
            (name, "binary-hierarchical", p, sweep[p]["top1"], sweep[p]["top2"])
        )
    return rows


def main(fast: bool = False):
    print("# Fig5 reproduction: dataset,mode,n_passes,top1,top2")
    t0 = time.time()
    rows = run_dataset("mnist-like", MNIST_LIKE, 128,
                       epochs=3 if fast else 8, noise=0.7)
    if not fast:
        rows += run_dataset("hg-like", HG_LIKE, 128, epochs=6, noise=0.6)
    for r in rows:
        print(f"fig5,{r[0]},{r[1]},{r[2]},{r[3]:.4f},{r[4]:.4f}")
    print(f"# fig5 done in {time.time() - t0:.1f}s")
    return rows


if __name__ == "__main__":
    main()
