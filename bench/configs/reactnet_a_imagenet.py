"""reactnet_a_imagenet: ReActNet-A on ImageNet-size images
(`reactnet_a_imagenet.json`).

A 3x3/2 stem on the thermometer-8 code of 224x224x3 images starts an
int32 residual stream at 112^2; 13 blocks of MobileNetV1's widths then
each run a binary 3x3 conv and a binary 1x1 conv, each reading the
stream through RSign thresholds, adding its own shortcut (the stream,
its 2x2 floor-average in a stride-2 block, or the stream twice where the
1x1 doubles the width) and bending the sum with RPReLU; the sign of the
7x7 sum-pool of the last stream feeds the CAM vote head of 1,000 rows.
The weights are drawn here from the seed: for each conv ±1 filters W,
each channel's BN-scale sign s (exactly half of each), magnitude m and
constant C, for a 1x1 that doubles the width two such convs; then each
conv's RSign alpha and RPReLU gamma, q, zeta; then the head's ±1 rows and
C.  The reference takes them as drawn (`bench/reference/reactnet.py`: a =
s * m, beta = q / 8, the two halves of a doubling 1x1 as two convs); the
program takes them in its deployment form, through `repro.deploy`: rows
s * W, the magnitudes m (`FoldedConvLayer.residual`), C, alpha, RPReLU
and the shortcut, the two halves of a doubling 1x1 as one c -> 2c conv.

The path runs one XLA program of int8 products on the MXU, named
`jit_picbnn_votes_off` in a trace.  Its work and bytes:

  ops   2 per binary multiply-accumulate, counted over full windows (pad
        positions count though they add 0), and the head's rows over the
        1,024 pooled bits and the bias cells
  bytes per row: the float32 pixels the program reads and the int32
        votes; per call: the binary weights, one bit each, without the
        head's bias cells.  The residual stream the program writes is
        not algorithmic work (`stream_elements_per_row` counts it for
        the `kernel.stream_stored_bytes` counter's check)
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import model as M

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())


def _convs(cfg: dict):
    """[(c_in, c_out, k, stride, shortcut, conv side)] of the stem and
    every sub-block, from `bench/reference/reactnet.py`'s plan of the
    file."""
    from bench.reference.reactnet import layers

    side, out = cfg["side"], []
    for c_in, c_out, k, stride, shortcut in layers(cfg):
        side = -(-side // stride)  # SAME
        out.append((c_in, c_out, k, stride, shortcut, side))
    return out


def macs_per_row(cfg: dict = CONFIG) -> int:
    """Binary MACs of one inference, the head's bias cells included."""
    macs = sum(side * side * c_out * k * k * c_in
               for c_in, c_out, k, _, _, side in _convs(cfg))
    return macs + cfg["n_classes"] * (_n_feat(cfg) + cfg["bias_cells"])


def _n_feat(cfg: dict) -> int:
    return cfg["blocks"][-1][0]


def ops_per_row(cfg: dict = CONFIG) -> int:
    return 2 * macs_per_row(cfg)


def weight_bits(cfg: dict = CONFIG) -> int:
    """Binary weights of the net, without the head's bias cells."""
    bits = sum(c_in * c_out * k * k for c_in, c_out, k, *_ in _convs(cfg))
    return bits + cfg["n_classes"] * _n_feat(cfg)


def in_bits_per_row(cfg: dict = CONFIG) -> int:
    """Bits of the float32 pixels of one row, which the program reads."""
    return 32 * cfg["side"] ** 2 * cfg["channels"]


def stream_elements_per_row(cfg: dict = CONFIG) -> int:
    """int32 stream elements the stem and each sub-block write for one
    row, a doubling 1x1 at its full width."""
    return sum(side * side * c_out for _, c_out, *_, side in _convs(cfg))


def cnn_config(cfg: dict = CONFIG):
    """The program's `CNNConfig` of the file: every conv residual and
    SAME, every one but the stem with RSign and RPReLU, the last one
    sum-pooling its global_pool x global_pool map."""
    from repro.core.binarize import InputEncoding
    from repro.core.convnet import CNNConfig, ConvSpec

    convs = _convs(cfg)
    specs = tuple(
        ConvSpec(k, c_out, stride, "same",
                 cfg["global_pool"] if i == len(convs) - 1 else 1,
                 residual=True, shortcut=shortcut, react=i > 0)
        for i, (_, c_out, k, stride, shortcut, _) in enumerate(convs))
    enc = cfg["encoding"]
    return CNNConfig(
        side=cfg["side"], channels=cfg["channels"],
        encoding=InputEncoding(enc["kind"], enc["width"]), conv=specs,
        hidden=(), n_classes=cfg["n_classes"], bias_cells=cfg["bias_cells"])


def build(seed: int, cfg: dict = CONFIG, **compile_options) -> M.Model:
    """The configuration `cfg` with weights from `seed`, deployed through
    `repro.deploy.deploy`."""
    import jax.numpy as jnp

    from bench.reference import bnn, reactnet as ref
    from repro.core.bnn import FoldedLayer
    from repro.core.convnet import FoldedConvLayer, RPReLU
    from repro.core.ensemble import EnsembleConfig
    from repro.deploy import deploy

    bias, c_max = cfg["bias_cells"], cfg["c_max"]
    rng = np.random.default_rng(M.seeds(seed)["weights"])

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    def draw(c_in, c_out, k):
        """±1 filters W, s, m and C of one conv: the program's (rows
        s * W, m, C) and the reference's (W HWIO, s * m, C)."""
        w = M.pm1(rng, (c_out, k, k, c_in))
        s = rng.permutation(np.resize(np.int8([1, -1]), c_out))
        m = rng.integers(1, cfg["m_max"] + 1, c_out)
        c = rng.integers(-c_max, c_max + 1, c_out)
        return ((s[:, None, None, None] * w).astype(np.int8), m, c), (
            f32(w.transpose(1, 2, 3, 0)), f32(s * m), f32(c))

    def shifts(n):
        return rng.integers(-c_max, c_max + 1, n)

    net = cnn_config(cfg)
    folded, params = [], []
    for (c_in, c_out, k, *_), spec in zip(_convs(cfg), net.conv):
        halves = 2 if spec.shortcut == "dup" else 1
        drawn = [draw(c_in, c_out // halves, k) for _ in range(halves)]
        rows, m, c = (np.concatenate(a) for a in zip(*(d[0] for d in drawn)))
        alpha = rprelu = prelu = None
        if spec.react:  # all but the stem, which reads pixels
            alpha = shifts(c_in)
            rprelu = RPReLU(gamma=shifts(c_out),
                            q=rng.integers(0, cfg["q_max"] + 1, c_out),
                            zeta=shifts(c_out))
            prelu = (f32(rprelu.gamma), f32(rprelu.q) / 8, f32(rprelu.zeta))
        folded.append(FoldedConvLayer(
            weights_pm1=rows, c=c, stride=spec.stride, padding=spec.padding,
            pool=spec.pool, residual=m, shortcut=spec.shortcut, alpha=alpha,
            rprelu=rprelu))
        params.append(([d[1] for d in drawn],
                       None if alpha is None else f32(alpha), prelu))
    n_feat = _n_feat(cfg)
    w_head = M.pm1(rng, (cfg["n_classes"], n_feat))
    c_head = M.fold_c(rng, cfg["n_classes"], n_feat, c_max, bias)
    folded.append(FoldedLayer(weights_pm1=w_head, c=c_head))
    dep = deploy(folded, config=net,
                 ens_cfg=EnsembleConfig(thresholds=tuple(cfg["sweep"]),
                                        bias_cells=bias),
                 **compile_options)
    head = [(jnp.asarray(w_head),
             jnp.asarray(bnn.head_c(c_head, bias), jnp.int32))]

    def hd(x, dtype=None):
        return ref.reactnet_hd(x, cfg, params, head, bnn.rounder(dtype))

    n_in = cfg["side"] ** 2 * cfg["channels"]

    def rows(rng, n):
        k = rng.integers(0, 256, (n, n_in), dtype=np.uint8)
        return k / np.float32(255.0)

    return M.Model(
        name=cfg["name"], deployment=dep, n_in=n_in,
        n_classes=cfg["n_classes"], kernel=cfg["kernel"],
        ops_per_row=ops_per_row(cfg), weight_bits=weight_bits(cfg),
        in_bits_per_row=in_bits_per_row(cfg), rows=rows, hd=hd,
        thresholds=bnn.head_thresholds(n_feat, bias, cfg["sweep"]))
