"""binarynet_cifar10: BinaryNet's CIFAR-10 ConvNet (`binarynet_cifar10.json`).

(2x128C3)-MP2-(2x256C3)-MP2-(2x512C3)-MP2-(2x1024FC)-10 on 32x32x3
images, thermometer-8 input per channel and the CAM vote head.  The
weights are drawn here from the seed: for each conv layer ±1 filters W,
each channel's BN-scale sign s (exactly half of each) and its constant
C; for each FC layer ±1 rows and C.  The reference takes them as drawn
(`bench/reference/conv.py`: sign(s * maxpool(conv(W)) + C)); the
program takes them in its deployment form, through `repro.deploy`: rows
s * W, C and the pool polarity s (`FoldedConvLayer.pool_sign`).

The path runs one XLA program of ±1 int8 products on the MXU, named
`jit_picbnn_votes_off` in a trace.  Its work and bytes:

  ops   2 per binary multiply-accumulate, counted over full 3x3 windows
        (the usual convention: pad positions count though they add 0),
        plus the head's rows over the last hidden width and the bias
        cells
  bytes per row: the packed input bits and the int32 votes; per call:
        the binary weights, one bit each, without the head's bias cells
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import model as M

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())


def _layers(cfg: dict):
    """[(k, c_in, c_out, conv side)] per conv layer, the flat width and
    the FC sizes (flat, *hidden, n_classes)."""
    side = cfg["side"]
    c_in = cfg["channels"] * cfg["encoding"]["width"]
    convs = []
    for spec in cfg["conv"]:
        k, s = spec["k"], spec["stride"]
        if spec["padding"] == "same":
            conv_side = -(-side // s)
        else:
            conv_side = (side - k) // s + 1
        convs.append((k, c_in, spec["c_out"], conv_side))
        side, c_in = conv_side // spec["pool"], spec["c_out"]
    flat = side * side * c_in
    return convs, (flat, *cfg["hidden"], cfg["n_classes"])


def macs_per_row(cfg: dict = CONFIG) -> int:
    """Binary MACs of one inference, the head's bias cells included."""
    convs, fc = _layers(cfg)
    macs = sum(s * s * o * k * k * i for k, i, o, s in convs)
    macs += sum(i * o for i, o in zip(fc[:-2], fc[1:-1]))
    return macs + fc[-1] * (fc[-2] + cfg["bias_cells"])


def ops_per_row(cfg: dict = CONFIG) -> int:
    return 2 * macs_per_row(cfg)


def weight_bits(cfg: dict = CONFIG) -> int:
    """Binary weights of the net, without the head's bias cells."""
    convs, fc = _layers(cfg)
    return (sum(k * k * i * o for k, i, o, _ in convs)
            + sum(i * o for i, o in zip(fc[:-1], fc[1:])))


def in_bits_per_row(cfg: dict = CONFIG) -> int:
    """Packed input bits of one row: the encoded image."""
    return cfg["side"] ** 2 * cfg["channels"] * cfg["encoding"]["width"]


def build(seed: int, cfg: dict = CONFIG, **compile_options) -> M.Model:
    """The configuration `cfg` with weights from `seed`, deployed through
    `repro.deploy.deploy`."""
    import jax.numpy as jnp

    from bench.reference import bnn, conv as ref
    from repro.core.binarize import InputEncoding
    from repro.core.bnn import FoldedLayer
    from repro.core.convnet import CNNConfig, ConvSpec, FoldedConvLayer
    from repro.core.ensemble import EnsembleConfig
    from repro.deploy import deploy

    bias = cfg["bias_cells"]
    rng = np.random.default_rng(M.seeds(seed)["weights"])
    convs, fc_sizes = _layers(cfg)
    folded, conv_params = [], []
    for (k, c_in, c_out, _), spec in zip(convs, cfg["conv"]):
        w = M.pm1(rng, (c_out, k, k, c_in))
        s = rng.permutation(np.resize(np.int8([1, -1]), c_out))
        c = M.fold_c(rng, c_out, k * k * c_in, cfg["c_max"], bias)
        folded.append(FoldedConvLayer(
            weights_pm1=(s[:, None, None, None] * w).astype(np.int8), c=c,
            stride=spec["stride"], padding=spec["padding"],
            pool=spec["pool"], pool_sign=s if spec["pool"] > 1 else None))
        conv_params.append((jnp.asarray(w.transpose(1, 2, 3, 0),
                                        jnp.float32),
                            jnp.asarray(s, jnp.float32),
                            jnp.asarray(c, jnp.float32)))
    fc = [(M.pm1(rng, (o, i)), M.fold_c(rng, o, i, cfg["c_max"], bias))
          for i, o in zip(fc_sizes[:-1], fc_sizes[1:])]
    folded += [FoldedLayer(weights_pm1=w, c=c) for w, c in fc]
    enc = cfg["encoding"]
    net = CNNConfig(
        side=cfg["side"], channels=cfg["channels"],
        encoding=InputEncoding(enc["kind"], enc["width"]),
        conv=tuple(ConvSpec(**spec) for spec in cfg["conv"]),
        hidden=tuple(cfg["hidden"]), n_classes=cfg["n_classes"],
        bias_cells=bias)
    dep = deploy(folded, config=net,
                 ens_cfg=EnsembleConfig(thresholds=tuple(cfg["sweep"]),
                                        bias_cells=bias),
                 **compile_options)
    fc_params = [(jnp.asarray(w), jnp.asarray(c, jnp.int32)) for w, c in fc]
    fc_params[-1] = (fc_params[-1][0],
                     jnp.asarray(bnn.head_c(fc[-1][1], bias), jnp.int32))

    def hd(x, dtype=None):
        return ref.conv_hd(x, cfg, conv_params, fc_params,
                           bnn.rounder(dtype))

    n_in = cfg["side"] ** 2 * cfg["channels"]

    def rows(rng, n):
        return (rng.integers(0, 256, (n, n_in)) / 255.0).astype(np.float32)

    return M.Model(
        name=cfg["name"], deployment=dep, n_in=n_in,
        n_classes=cfg["n_classes"], kernel=cfg["kernel"],
        ops_per_row=ops_per_row(cfg), weight_bits=weight_bits(cfg),
        in_bits_per_row=in_bits_per_row(cfg), rows=rows, hd=hd,
        thresholds=bnn.head_thresholds(fc_sizes[-2], bias, cfg["sweep"]))
