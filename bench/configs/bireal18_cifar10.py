"""bireal18_cifar10: binary ResNet-18 with Bi-Real shortcuts on CIFAR-10
(`bireal18_cifar10.json`).

A 3x3 stem on the thermometer-8 code of 32x32x3 images starts an int32
residual stream; 16 binary 3x3 convs in stages of 64@32^2, 128@16^2,
256@8^2 and 512@4^2 each add onto it, the first of stages 2-4 with
stride 2 and the downsample shortcut; the sign of its 4x4 sum-pool feeds
the CAM vote head.  The weights are drawn here from the seed: for each
conv ±1 filters W, each channel's BN-scale sign s (exactly half of each),
magnitude m and constant C, and the same for each downsample's 1x1 conv;
then the head's ±1 rows and C.  The reference takes them as drawn
(`bench/reference/resnet.py`: a = s * m); the program takes them in its
deployment form, through `repro.deploy`: rows s * W, the magnitudes m
(`FoldedConvLayer.residual`), C and the shortcut.

The path runs one XLA program of int8 products on the MXU, named
`jit_picbnn_votes_off` in a trace.  Its work and bytes:

  ops   2 per binary multiply-accumulate, counted over full 3x3 windows
        (pad positions count though they add 0), the downsample convs,
        and the head's rows over the 512 pooled bits and the bias cells
  bytes per row: the float32 pixels the program reads and the int32
        votes; per call: the binary weights, one bit each, without the
        head's bias cells.  The residual stream the program writes is
        not algorithmic work (`stream_elements_per_row` counts it for
        the `kernel.stream_bytes` counter's check)
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench import model as M

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())


def _convs(cfg: dict):
    """[(c_in, c_out, stride, shortcut, conv side)] of the stem and every
    conv, from `bench/reference/resnet.py`'s plan of the file."""
    from bench.reference.resnet import layers

    side, out = cfg["side"], []
    for c_in, c_out, stride, shortcut in layers(cfg):
        side = -(-side // stride)  # SAME
        out.append((c_in, c_out, stride, shortcut, side))
    return out


def macs_per_row(cfg: dict = CONFIG) -> int:
    """Binary MACs of one inference, the head's bias cells included."""
    k2 = cfg["k"] ** 2
    macs = 0
    for c_in, c_out, _, shortcut, side in _convs(cfg):
        macs += side * side * c_out * c_in * (k2 + (shortcut == "down"))
    return macs + cfg["n_classes"] * (cfg["stages"][-1] + cfg["bias_cells"])


def ops_per_row(cfg: dict = CONFIG) -> int:
    return 2 * macs_per_row(cfg)


def weight_bits(cfg: dict = CONFIG) -> int:
    """Binary weights of the net, without the head's bias cells."""
    k2 = cfg["k"] ** 2
    bits = sum(c_in * c_out * (k2 + (shortcut == "down"))
               for c_in, c_out, _, shortcut, _ in _convs(cfg))
    return bits + cfg["n_classes"] * cfg["stages"][-1]


def in_bits_per_row(cfg: dict = CONFIG) -> int:
    """Bits of the float32 pixels of one row, which the program reads."""
    return 32 * cfg["side"] ** 2 * cfg["channels"]


def stream_elements_per_row(cfg: dict = CONFIG) -> int:
    """int32 stream elements the stem and each conv write for one row."""
    return sum(side * side * c_out for _, c_out, _, _, side in _convs(cfg))


def cnn_config(cfg: dict = CONFIG):
    """The program's `CNNConfig` of the file: every conv residual, SAME,
    the last one sum-pooling its global_pool x global_pool map."""
    from repro.core.binarize import InputEncoding
    from repro.core.convnet import CNNConfig, ConvSpec

    convs = _convs(cfg)
    specs = tuple(
        ConvSpec(cfg["k"], c_out, stride, "same",
                 cfg["global_pool"] if i == len(convs) - 1 else 1,
                 residual=True, shortcut=shortcut)
        for i, (_, c_out, stride, shortcut, _) in enumerate(convs))
    enc = cfg["encoding"]
    return CNNConfig(
        side=cfg["side"], channels=cfg["channels"],
        encoding=InputEncoding(enc["kind"], enc["width"]), conv=specs,
        hidden=(), n_classes=cfg["n_classes"], bias_cells=cfg["bias_cells"])


def build(seed: int, cfg: dict = CONFIG, **compile_options) -> M.Model:
    """The configuration `cfg` with weights from `seed`, deployed through
    `repro.deploy.deploy`."""
    import jax.numpy as jnp

    from bench.reference import bnn, resnet as ref
    from repro.core.bnn import FoldedLayer
    from repro.core.convnet import FoldedConvLayer
    from repro.core.ensemble import EnsembleConfig
    from repro.deploy import deploy

    bias, k = cfg["bias_cells"], cfg["k"]
    rng = np.random.default_rng(M.seeds(seed)["weights"])

    def draw(c_in, c_out, kk):
        """±1 filters W, s, m and C of one conv: the program's fields
        (rows s * W, magnitudes m, C) and the reference's (W, s * m, C)."""
        w = M.pm1(rng, (c_out, kk, kk, c_in))
        s = rng.permutation(np.resize(np.int8([1, -1]), c_out))
        m = rng.integers(1, cfg["m_max"] + 1, c_out)
        c = rng.integers(-cfg["c_max"], cfg["c_max"] + 1, c_out)
        fields = dict(
            weights_pm1=(s[:, None, None, None] * w).astype(np.int8),
            c=c, residual=m)
        plain = (jnp.asarray(w.transpose(1, 2, 3, 0), jnp.float32),
                 jnp.asarray(s * m, jnp.float32),
                 jnp.asarray(c, jnp.float32))
        return fields, plain

    net = cnn_config(cfg)
    folded, params = [], []
    for (c_in, *_), spec in zip(_convs(cfg), net.conv):
        fields, plain = draw(c_in, spec.c_out, k)
        down, down_plain = (draw(c_in, spec.c_out, 1)
                            if spec.shortcut == "down" else (None, None))
        folded.append(FoldedConvLayer(
            **fields, stride=spec.stride, padding=spec.padding,
            pool=spec.pool, shortcut=spec.shortcut,
            down=None if down is None else FoldedConvLayer(**down)))
        params.append((*plain, down_plain))
    n_feat = cfg["stages"][-1]
    w_head = M.pm1(rng, (cfg["n_classes"], n_feat))
    c_head = M.fold_c(rng, cfg["n_classes"], n_feat, cfg["c_max"], bias)
    folded.append(FoldedLayer(weights_pm1=w_head, c=c_head))
    dep = deploy(folded, config=net,
                 ens_cfg=EnsembleConfig(thresholds=tuple(cfg["sweep"]),
                                        bias_cells=bias),
                 **compile_options)
    head = [(jnp.asarray(w_head),
             jnp.asarray(bnn.head_c(c_head, bias), jnp.int32))]

    def hd(x, dtype=None):
        return ref.resnet_hd(x, cfg, params, head, bnn.rounder(dtype))

    n_in = cfg["side"] ** 2 * cfg["channels"]

    def rows(rng, n):
        return (rng.integers(0, 256, (n, n_in)) / 255.0).astype(np.float32)

    return M.Model(
        name=cfg["name"], deployment=dep, n_in=n_in,
        n_classes=cfg["n_classes"], kernel=cfg["kernel"],
        ops_per_row=ops_per_row(cfg), weight_bits=weight_bits(cfg),
        in_bits_per_row=in_bits_per_row(cfg), rows=rows, hd=hd,
        thresholds=bnn.head_thresholds(n_feat, bias, cfg["sweep"]))
