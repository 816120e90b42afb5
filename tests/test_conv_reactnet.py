"""ReActNet: RSign, RPReLU and the "avg" and "dup" shortcuts on the
residual stream.

The deployed path must give the unpacked oracle's votes exactly
(`kernels.ref.stream_layer_ref`: sign(x - alpha), m * conv + C + the
shortcut, then RPReLU, in float32) on a reduced ReActNet with a stride-2
stem, an identity block, a block whose 1x1 doubles the width ("dup") and
a stride-2 block ("avg" with "dup"); that comparison on two seeds, and
each new field moving the votes, are cases of tests/test_conv_resnet.py's
parametrised tests.  Here: malformed layers and stacks are refused, a
saved deployment keeps every new field, the server answers as the
oracle, the proven bound at published widths is the reckoned total (an
int32 stream throughout), and a stream driven to its bound through
RPReLU's negative branch and an "avg" shortcut still equals the oracle.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs.paper_cnn import (MOBILENET_V1_BLOCKS,
                                     REACTNET_A_IMAGENET, deploy_cnn,
                                     reactnet_convs)
from repro.core import convnet
from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec, FoldedConvLayer, RPReLU
from repro.deploy import Deployment
from repro.kernels import fused_conv, ref
from repro.serve.picbnn import BatchingPolicy, PicBnnServer
from repro.spec import InferenceSpec

# the same reduced net as tests/test_conv_resnet.py's "reactnet-16"
SMALL = CNNConfig(
    side=16, channels=3, encoding=InputEncoding("thermometer", 8),
    conv=reactnet_convs(8, ((16, 1), (16, 1), (32, 2)), 4), hidden=(),
    n_classes=10)


def _images(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (n, cfg.n_in)) / 255.0).astype(np.float32)


def _oracle(cfg, folded, head, x):
    return np.asarray(ref.conv_votes_ref(folded, head, x, cfg.encoding,
                                         cfg.side, cfg.channels))


def _layer(c_out, c_in, k=3, **kw):
    rng = np.random.default_rng(c_out + c_in)
    return FoldedConvLayer(
        weights_pm1=rng.choice([-1, 1], (c_out, k, k, c_in)).astype(np.int8),
        c=np.zeros(c_out, np.int64), padding="same", **kw)


def _ones(n):
    return np.ones(n, np.int64)


def _rp(n, q=4):
    return RPReLU(gamma=np.zeros(n, np.int64), q=np.full(n, q),
                  zeta=np.zeros(n, np.int64))


BAD_LAYERS = {
    "avg-stride-1": lambda: _layer(8, 8, residual=_ones(8), shortcut="avg"),
    "avg-wider": lambda: _layer(16, 8, residual=_ones(16), stride=2,
                                shortcut="avg"),
    "dup-not-twice": lambda: _layer(12, 8, k=1, residual=_ones(12),
                                    shortcut="dup"),
    "q-above-8": lambda: _rp(8, q=9),
    "q-below-0": lambda: _rp(8, q=-1),
    "rprelu-ragged": lambda: RPReLU(gamma=np.zeros(8), q=np.ones(7),
                                    zeta=np.zeros(8)),
    "rprelu-of-other-width": lambda: _layer(8, 8, residual=_ones(8),
                                            rprelu=_rp(16)),
    "alpha-of-c-out": lambda: _layer(16, 8, k=1, residual=_ones(16),
                                     shortcut="dup", alpha=_ones(16)),
    "rprelu-on-a-plain-layer": lambda: _layer(8, 8, rprelu=_rp(8)),
    "alpha-on-a-plain-layer": lambda: _layer(8, 8, alpha=_ones(8)),
    "spec-avg-stride-1": lambda: ConvSpec(3, 8, 1, "same", residual=True,
                                          shortcut="avg"),
    "spec-react-plain": lambda: ConvSpec(3, 8, 1, "same", react=True),
}


@pytest.mark.parametrize("case", sorted(BAD_LAYERS))
def test_malformed_reactnet_layers_are_refused(case):
    with pytest.raises(ValueError):
        BAD_LAYERS[case]()


STEM = dict(c_out=8, c_in=24, residual=_ones(8))
BAD_STACKS = {
    # a 7x7 stream has no 2x2 floor-average of the stride-2 output
    "avg-odd-side": (7, [STEM, dict(c_out=8, c_in=8, residual=_ones(8),
                                    stride=2, shortcut="avg")]),
    # the stem reads pixels, not a stream: no RSign threshold for it
    "alpha-on-the-stem": (8, [dict(STEM, alpha=_ones(24))]),
    # a plain layer ends the stream: nothing for the next one's RSign
    "alpha-after-plain": (8, [dict(c_out=8, c_in=24),
                              dict(c_out=8, c_in=8, residual=_ones(8),
                                   alpha=_ones(8))]),
    # "dup" adds the 8x8 stream to a 4x4 output
    "dup-across-stride": (8, [STEM, dict(c_out=16, c_in=8, k=1, stride=2,
                                         residual=_ones(16),
                                         shortcut="dup")]),
}


@pytest.mark.parametrize("case", sorted(BAD_STACKS))
def test_reactnet_stacks_without_a_stream_to_read_are_refused(case):
    side, kws = BAD_STACKS[case]
    with pytest.raises(ValueError, match="shortcut|RSign"):
        fused_conv.conv_metas_for([_layer(**kw) for kw in kws], side)


def test_deployment_round_trip_keeps_the_reactnet_fields(tmp_path):
    folded = convnet.random_folded_cnn(SMALL, seed=6)
    dep = deploy_cnn(SMALL, folded, min_bucket=8)
    back = Deployment.load(dep.save(tmp_path / "d"))
    for a, b in zip(dep.conv_layers, back.conv_layers):
        assert (a.shortcut, a.k, a.stride, a.pool) == (
            b.shortcut, b.k, b.stride, b.pool)
        np.testing.assert_array_equal(a.weights_pm1, b.weights_pm1)
        np.testing.assert_array_equal(a.residual, b.residual)
        assert (a.alpha is None) == (b.alpha is None)
        assert (a.rprelu is None) == (b.rprelu is None)
        if a.alpha is not None:
            np.testing.assert_array_equal(a.alpha, b.alpha)
        if a.rprelu is not None:
            for f in ("gamma", "q", "zeta"):
                np.testing.assert_array_equal(getattr(a.rprelu, f),
                                              getattr(b.rprelu, f))
    assert sum(l.rprelu is not None for l in back.conv_layers) == 6
    x = _images(SMALL, 9, seed=7)
    np.testing.assert_array_equal(np.asarray(back.run(x, InferenceSpec())),
                                  np.asarray(dep.run(x, InferenceSpec())))


def test_server_answers_a_reactnet_deployment_as_the_oracle(tmp_path):
    folded = convnet.random_folded_cnn(SMALL, seed=8)
    dep = deploy_cnn(SMALL, folded, min_bucket=8, max_bucket=32)
    x = _images(SMALL, 20, seed=9)
    srv = PicBnnServer(BatchingPolicy(max_batch=16, max_wait_us=200))
    srv.register("reactnet", dep.save(tmp_path / "reactnet"))
    with srv:
        res = srv.submit_many("reactnet", x).results(timeout=120)
    np.testing.assert_array_equal(
        np.stack([r.votes for r in res]),
        _oracle(SMALL, folded, dep.pipeline().head, x))


def test_reactnet_a_geometry_at_published_widths():
    cfg = REACTNET_A_IMAGENET
    assert len(MOBILENET_V1_BLOCKS) == 13 and len(cfg.conv) == 27
    # each block's output side, the last one's after the 7x7 global pool
    assert cfg.feature_sides()[3::2] == [112] + [56] * 2 + [28] * 2 \
        + [14] * 6 + [7, 1]
    assert [s.shortcut for s in cfg.conv].count("avg") == 4
    assert [s.shortcut for s in cfg.conv].count("dup") == 5
    assert cfg.fc_sizes == (1024, 1000)


def test_the_published_stream_bound_is_the_reckoned_total():
    """Every magnitude 4 and one channel of each layer at |C| = |gamma| =
    |zeta| = 40: the stem adds 216 * 4 + 40, every other conv 4 k^2 c_in
    + 120, no shortcut restarts the stream, and the 7x7 global pool sums
    49 values: an int32 stream throughout, and below 2^24 for the
    float32 oracle."""
    cfg = REACTNET_A_IMAGENET
    drawn = convnet.random_folded_cnn(cfg, seed=1)[:len(cfg.conv)]

    def extreme(layer):
        c = np.asarray(layer.c, np.int64).copy()
        c[0] = -40
        rp = layer.rprelu
        if rp is not None:
            g, z = rp.gamma.copy(), rp.zeta.copy()
            g[0], z[0] = 40, -40
            rp = dataclasses.replace(rp, gamma=g, zeta=z)
        return dataclasses.replace(
            layer, residual=np.full(layer.c_out, 4), c=c, rprelu=rp)

    layers = [extreme(l) for l in drawn]
    total = 9 * 24 * 4 + 40 + sum(4 * s.k ** 2 * c_in + 120 for s, c_in in
                                  zip(cfg.conv[1:],
                                      cfg.feature_channels()[1:-1]))
    assert total == 202_424
    bounds = fused_conv.stream_bounds(layers)
    assert set(bounds) == {49 * total}
    assert 49 * total < 2 ** 24
    metas = fused_conv.conv_metas_for(layers, cfg.side)
    assert {str(m.stream_dtype) for m in metas} == {"int32"}
    assert fused_conv.stream_bytes_per_row(metas) == 20_170_752
    assert fused_conv.stream_stored_bytes_per_row(metas) == 20_170_752


# a stem and an identity layer whose RPReLU takes the stream's interior
# to the bound of its stored type, then a stride-2 "avg" layer whose
# interior reaches the stream's bound through the same branch
EDGE = CNNConfig(
    side=8, channels=1, encoding=InputEncoding("thermometer", 4),
    conv=(ConvSpec(3, 16, 1, "same", residual=True),
          ConvSpec(3, 16, 1, "same", residual=True, shortcut="identity",
                   react=True),
          ConvSpec(3, 16, 2, "same", residual=True, shortcut="avg",
                   react=True)),
    hidden=(), n_classes=10)


def _edge_layers(bound: int, polarity: int):
    """EDGE's conv layers.  On all-+1 input the stem's rows are all
    `polarity` at m = 127 and the next two layers' rows all +1, so each
    interior value is `polarity` times the most its layer adds, C and
    RPReLU's shifts taking the same sign: RPReLU's slope is 1 (q = 8) on
    its branch below gamma for polarity -1, and the stream's interior
    reaches polarity * bound after the "avg" layer.  Half of that
    layer's channels take q = 3 instead: floors of negative values."""
    def full(v):
        return np.full(16, v, np.int64)

    t_stem = 9 * 4 * 127 + 40
    t_mid = 9 * 16 * 127 + 40
    rest = bound - t_stem - t_mid - (9 * 16 + 40)  # the last two shifts

    def rp(g, z, q):
        return RPReLU(gamma=full(-polarity * g), q=q, zeta=full(polarity * z))

    stem = FoldedConvLayer(
        weights_pm1=np.full((16, 3, 3, 4), polarity, np.int8),
        c=full(polarity * 40), padding="same", residual=full(127))
    mid = FoldedConvLayer(
        weights_pm1=np.ones((16, 3, 3, 16), np.int8),
        c=full(polarity * 40), padding="same", residual=full(127),
        shortcut="identity", alpha=full(-polarity * 40),
        rprelu=rp(rest // 2, rest - rest // 2, full(8)))
    q = np.where(np.arange(16) % 2 == 0, 8, 3)
    last = FoldedConvLayer(
        weights_pm1=np.ones((16, 3, 3, 16), np.int8),
        c=full(polarity * 40), stride=2, padding="same",
        residual=full(1), shortcut="avg", alpha=full(polarity * 7),
        rprelu=rp(0, 0, q))
    return [stem, mid, last]


@pytest.mark.parametrize("polarity", [1, -1], ids=["pos", "neg"])
@pytest.mark.parametrize("bound, stored", [(32_767, "int16"),
                                           (32_768, "int32")])
def test_a_stream_at_its_bound_through_rprelu_and_avg_equals_the_oracle(
        bound, stored, polarity):
    convs = _edge_layers(bound, polarity)
    assert fused_conv.stream_bounds(convs) == (bound,) * 3
    metas = fused_conv.conv_metas_for(convs, EDGE.side)
    assert [str(m.stream_dtype) for m in metas] == [stored] * 3
    folded = convs + convnet.random_folded_cnn(EDGE, seed=5)[3:]
    dep = deploy_cnn(EDGE, folded, min_bucket=8)
    x = np.ones((8, EDGE.n_in), np.float32)  # every thermometer bit fires
    # the interior of the last layer's 4x4 map is polarity * bound in
    # its channels of q = 8
    x01 = dep.image_encoding.encode_image_pm1(
        x[:1].reshape(1, 8, 8, 1))
    stream = None
    for layer in convs:
        stream = ref.stream_layer_ref(
            x01 if stream is None else np.where(stream >= 0, 1.0, -1.0),
            stream, layer)
    np.testing.assert_array_equal(np.asarray(stream)[0, 1:3, 1:3, ::2],
                                  polarity * bound)
    want = _oracle(EDGE, folded, dep.pipeline().head, x)
    np.testing.assert_array_equal(np.asarray(dep.run(x, InferenceSpec())),
                                  want)
