"""What the harness needs of one model configuration, and its weights.

A configuration module (`bench/configs/<name>.py`) reads its JSON file
and returns a `Model` from `build(seed)`: the program's `Deployment`,
built through the normal path, beside the same weights in the plain
form the reference takes.  The weights are made here, from the seed,
and handed to the program; nothing the program makes is given to the
reference.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np


@dataclasses.dataclass
class Model:
    """One configuration as built for one run."""

    name: str
    deployment: object  # repro.deploy.Deployment
    n_in: int  # request row width
    n_classes: int
    kernel: str  # the Pallas kernel the noiseless path runs
    ops_per_row: float  # 2 per binary multiply-accumulate
    weight_bits: int  # binary weights the kernel reads per call
    in_bits_per_row: int  # input bits the kernel reads per row
    rows: Callable  # (rng, n) -> request rows [n, n_in] float32
    hd: Callable  # (x, rnd) -> reference head distances [n, C]
    thresholds: np.ndarray  # [P] float32 thresholds

    def kernel_ops(self, rows: int) -> float:
        """Operations of the kernel over `rows` rows."""
        return rows * self.ops_per_row

    def kernel_bytes(self, rows: int, calls: int = 1) -> float:
        """Algorithmic bytes of `calls` kernel calls over `rows` rows in
        all: input bits and int32 votes per row, plus the weight bits
        once per call."""
        return (rows * (self.in_bits_per_row / 8 + 4 * self.n_classes)
                + calls * self.weight_bits / 8)


def seeds(seed: int) -> dict:
    """Independent seed sequences for weights, request rows, arrival
    times and the checked sample, all from one `--seed` (any
    non-negative integer)."""
    names = ("weights", "traffic", "arrivals", "sample")
    return dict(zip(names, np.random.SeedSequence(int(seed)).spawn(4)))


def pm1(rng, shape) -> np.ndarray:
    """Uniform ±1 int8 array."""
    return (rng.integers(0, 2, shape, dtype=np.int8) * 2 - 1).astype(np.int8)


def fold_c(rng, n_out: int, n_in: int, c_max: int, bias_cells: int):
    """Folded-BN constants C in [-c_max, c_max] with the parity that
    leaves sign(W x + C) no dead zone (W x has the parity of n_in), kept
    within the bias-cell budget."""
    c = rng.integers(-c_max, c_max + 1, n_out).astype(np.int64)
    c = np.where((c + n_in) % 2 == 0, c + 1, c)
    c = np.clip(c, -bias_cells, bias_cells)
    return np.where((c + n_in) % 2 == 0, c - np.sign(c), c)


def mlp_ops_per_row(cfg: dict) -> int:
    """2 x binary MACs of one inference of an MLP configuration: every
    FC layer, and the head's rows over the last hidden width plus the
    bias cells."""
    sizes = cfg["layer_sizes"]
    macs = sum(i * o for i, o in zip(sizes[:-2], sizes[1:-1]))
    macs += sizes[-1] * (sizes[-2] + cfg["bias_cells"])
    return 2 * macs


def mlp(cfg: dict, seed: int, **compile_options) -> Model:
    """An MLP configuration with weights from `seed`, deployed through
    `deploy_mlp`.  Request rows are ±1 activations [n_in]; the path runs
    the `fused_mlp` kernel."""
    import jax.numpy as jnp

    from bench.reference import bnn as ref
    from repro.configs.paper_mlp import deploy_mlp
    from repro.core.bnn import FoldedLayer, MLPConfig
    from repro.core.ensemble import EnsembleConfig

    sizes, bias = cfg["layer_sizes"], cfg["bias_cells"]
    rng = np.random.default_rng(seeds(seed)["weights"])
    fc = [(pm1(rng, (o, i)), fold_c(rng, o, i, cfg["c_max"], bias))
          for i, o in zip(sizes[:-1], sizes[1:])]
    dep = deploy_mlp(
        MLPConfig(layer_sizes=tuple(sizes), bias_cells=bias),
        [FoldedLayer(weights_pm1=w, c=c) for w, c in fc],
        ens_cfg=EnsembleConfig(thresholds=tuple(cfg["sweep"]),
                               bias_cells=bias),
        **compile_options)
    params = [(jnp.asarray(w), jnp.asarray(c, jnp.int32)) for w, c in fc]
    params[-1] = (params[-1][0],
                  jnp.asarray(ref.head_c(fc[-1][1], bias), jnp.int32))

    def hd(x, dtype=None):
        rnd = ref.rounder(dtype)
        return ref.fc_hd(rnd(x), params, bias, rnd)

    def rows(rng, n):
        return pm1(rng, (n, sizes[0])).astype(np.float32)

    ops = mlp_ops_per_row(cfg)
    return Model(
        name=cfg["name"], deployment=dep, n_in=sizes[0],
        n_classes=sizes[-1], kernel=cfg["kernel"], ops_per_row=ops,
        weight_bits=ops // 2, in_bits_per_row=sizes[0], rows=rows, hd=hd,
        thresholds=ref.head_thresholds(sizes[-2], bias, cfg["sweep"]))
