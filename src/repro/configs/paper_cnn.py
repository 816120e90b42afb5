"""End-to-end-binary CNN configs for the paper's two image tasks.

The paper's headline property is *end-to-end* binarization: unlike
typical BNNs that keep "the input layer of a convolutional neural
network" in full precision, every layer here — input included — computes
on bits.  These configs instantiate that claim as small binary CNNs over
the same synthetic stand-in datasets the MLP workload uses
(`data/synthetic.py`):

  MNIST CNN (28x28, 10 classes):
      thermometer-8 input -> 3x3x32 s2 conv -> 3x3x32 s2 conv
      -> flatten 1152 -> FC 128 -> CAM head (10 rows, 33-pass vote)
  HG CNN (64x64, 20 classes):
      thermometer-4 input -> 3x3x32 s2 conv -> 3x3x32 s2 conv
      -> flatten 7200 -> FC 128 -> CAM head (20 rows, 33-pass vote)

Downsampling in these two is stride-2 VALID convs; the head row (128 +
64 bias cells) lands on the macro's 1024x128 logical bank configuration,
same as the paper MLPs.

  CIFAR-10 ConvNet (32x32x3, 10 classes; BinaryNet, Courbariaux et al.
  2016, arXiv:1602.02830, after BinaryConnect arXiv:1511.00363 Sec. 3.3):
      thermometer-8 per RGB channel (24 input channels)
      -> (2x128C3)-MP2-(2x256C3)-MP2-(2x512C3)-MP2 -> flatten 8192
      -> FC 1024 -> FC 1024 -> CAM head (10 rows, 33-pass vote)
  3x3 convs with zero padding 1 ("same"); each 2x2/2 max-pool sits
  between its conv and the batch norm, so after the fold it is the OR
  (BN scale > 0) or the AND (< 0) of the window's sign bits.  The
  thermometer input and the CAM head replace BinaryNet's real-valued
  first-layer input and its 10 float output units.

  Bi-Real-18 on CIFAR-10 (32x32x3, 10 classes; Bi-Real Net, Liu et al.
  2018, arXiv:1808.00278 Sec. 3.1, on ResNet-18's layout, He et al.
  2015, arXiv:1512.03385 Table 1, with the CIFAR stem):
      thermometer-8 per RGB channel -> 3x3x64 stem, which starts the
      int32 residual stream -> four stages of four binary 3x3 convs,
      64@32^2, 128@16^2, 256@8^2, 512@4^2, a shortcut around every conv
      (stages 2-4 open with stride 2 and the downsample shortcut)
      -> sign of the 4x4 sum-pool, 512 bits -> CAM head (10 rows)
  Every conv's BN is integer, a = s * m per channel plus C (DESIGN.md
  §10).

  ReActNet-A on ImageNet (224x224x3, 1000 classes; Liu et al. 2020,
  arXiv:2003.03488 Sec. 3.1-3.2, on MobileNetV1's layout, Howard et al.
  2017, arXiv:1704.04861 Table 1):
      thermometer-8 per RGB channel -> 3x3/2 x32 stem at 112^2, which
      starts the int32 residual stream -> 13 blocks of a binary 3x3 conv
      c -> c and a binary 1x1 conv c -> c', each with a shortcut of its
      own, output widths 64, 128, 128, 256, 256, 512 x 6, 1024, 1024;
      blocks 2, 4, 6 and 12 have stride 2 (112 -> 56 -> 28 -> 14 -> 7),
      their 3x3's shortcut the 2x2 floor-average ("avg"); a 1x1 that
      doubles the width is two c -> c convs concatenated, one c -> 2c
      conv here, its shortcut the stream twice ("dup"); every conv but
      the stem reads sign(x - alpha) (RSign) and applies RPReLU after
      its add -> sign of the 7x7 sum-pool, 1024 bits -> CAM head (1000
      rows)

`build_cnn_pipeline` is the one-call deployment path used by the
benchmarks, the serving registry, and the tests.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec
from repro.core.ensemble import EnsembleConfig, PAPER_THRESHOLDS

MNIST_CNN = CNNConfig(
    side=28,
    encoding=InputEncoding("thermometer", 8),
    conv=(ConvSpec(3, 32, 2), ConvSpec(3, 32, 2)),
    hidden=(128,),
    n_classes=10,
    bias_cells=64,
)

HG_CNN = CNNConfig(
    side=64,
    encoding=InputEncoding("thermometer", 4),
    conv=(ConvSpec(3, 32, 2), ConvSpec(3, 32, 2)),
    hidden=(128,),
    n_classes=20,
    bias_cells=64,
)

CIFAR10_CONVNET = CNNConfig(
    side=32,
    channels=3,
    encoding=InputEncoding("thermometer", 8),
    conv=(ConvSpec(3, 128, 1, "same"), ConvSpec(3, 128, 1, "same", 2),
          ConvSpec(3, 256, 1, "same"), ConvSpec(3, 256, 1, "same", 2),
          ConvSpec(3, 512, 1, "same"), ConvSpec(3, 512, 1, "same", 2)),
    hidden=(1024, 1024),
    n_classes=10,
    bias_cells=64,
)


def bireal_convs(widths: Sequence[int], convs_per_stage: int,
                 global_pool: int) -> tuple[ConvSpec, ...]:
    """Bi-Real Net's binary ResNet as a flat residual conv stack.

    A 3x3 stem of widths[0] channels starts the stream; each stage then
    has `convs_per_stage` 3x3 SAME convs of its width, each with a
    shortcut around it (two a basic block); every stage after the first
    opens with stride 2 and the downsample shortcut.  The last conv
    sum-pools its `global_pool` x `global_pool` map.
    """
    convs = [ConvSpec(3, widths[0], 1, "same", residual=True)]
    for i, width in enumerate(widths):
        for j in range(convs_per_stage):
            down = i > 0 and j == 0
            convs.append(ConvSpec(
                3, width, 2 if down else 1, "same", residual=True,
                shortcut="down" if down else "identity"))
    convs[-1] = dataclasses.replace(convs[-1], pool=global_pool)
    return tuple(convs)


BIREAL18_CIFAR10 = CNNConfig(
    side=32,
    channels=3,
    encoding=InputEncoding("thermometer", 8),
    conv=bireal_convs((64, 128, 256, 512), 4, 4),
    hidden=(),
    n_classes=10,
    bias_cells=64,
)


def reactnet_convs(stem: int, blocks: Sequence[tuple[int, int]],
                   global_pool: int) -> tuple[ConvSpec, ...]:
    """ReActNet's binary MobileNet as a flat residual conv stack.

    A 3x3/2 stem of `stem` channels starts the stream; each block
    (c_out, stride) then has a 3x3 conv of the incoming width c (shortcut
    "identity", or "avg" at stride 2) and a 1x1 conv to c_out (shortcut
    "identity", or "dup" where c_out = 2c), both with RSign and RPReLU.
    The last conv sum-pools its `global_pool` x `global_pool` map.
    """
    convs = [ConvSpec(3, stem, 2, "same", residual=True)]
    c = stem
    react = dict(residual=True, react=True)
    for c_out, stride in blocks:
        convs.append(ConvSpec(3, c, stride, "same",
                              shortcut="avg" if stride == 2 else "identity",
                              **react))
        convs.append(ConvSpec(1, c_out, 1, "same",
                              shortcut="dup" if c_out == 2 * c
                              else "identity", **react))
        c = c_out
    convs[-1] = dataclasses.replace(convs[-1], pool=global_pool)
    return tuple(convs)


#: MobileNetV1's 13 blocks (output width, stride), as ReActNet-A keeps them
MOBILENET_V1_BLOCKS = ((64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
                       (512, 2), *[(512, 1)] * 5, (1024, 2), (1024, 1))

REACTNET_A_IMAGENET = CNNConfig(
    side=224,
    channels=3,
    encoding=InputEncoding("thermometer", 8),
    conv=reactnet_convs(32, MOBILENET_V1_BLOCKS, 7),
    hidden=(),
    n_classes=1000,
    bias_cells=64,
)

CNN_ENSEMBLE = EnsembleConfig(
    thresholds=PAPER_THRESHOLDS, bias_cells=64, mode="fused"
)


def deploy_cnn(cfg: CNNConfig, model, *, noise=None, **kw):
    """Build the `deploy.Deployment` artifact for an end-to-end CNN.

    Thin wrapper over `deploy.deploy` that threads the config's image
    geometry and channels, binary input encoding, and bias cells.
    `model` is `convnet.fold_cnn` (trained), a trained params dict
    (folded here), or `convnet.random_folded_cnn` (weight-agnostic
    benchmarks/tests) output.  `.pipeline()` compiles lazily;
    `.save(dir)` persists for `PicBnnServer.register`.
    """
    from repro.deploy import deploy

    return deploy(model, config=cfg, noise=noise, **kw)


def build_cnn_pipeline(cfg: CNNConfig, folded, *, bq=None, noise=None,
                       **kw):
    """Compile a folded CNN into the fused end-to-end pipeline.

    `deploy_cnn(...).pipeline()` in one call — kept as the historical
    one-call deployment path used by benchmarks and tests.
    """
    opts = {k: v for k, v in dict(bq=bq, **kw).items()
            if v is not None}
    return deploy_cnn(cfg, folded, noise=noise, **opts).pipeline()
