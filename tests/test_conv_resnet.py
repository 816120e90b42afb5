"""Residual CNNs: an int32 stream through the conv vote program.

The deployed path (`Deployment` -> `CompiledPipeline.run`, and the
server) must give the unpacked oracle's votes exactly
(`kernels.ref.stream_layer_ref`: m * conv + C + shortcut in float32, the
next layer reading the stream's sign) on reduced Bi-Real ResNets with
identity and downsample shortcuts, stride 2 and a global sum-pool, and
on a net that leaves the stream for a plain pooled conv and an FC layer,
and on a reduced ReActNet (RSign, RPReLU, "avg" and "dup" shortcuts;
tests/test_conv_reactnet.py holds the rest of its cases); every residual
field, those of ReActNet among them, moves the votes alike;
a saved deployment keeps every residual field; a stack whose shortcut
finds no stream of its shape is refused; and each vote dispatch counts
the stream its program writes.

The stream is stored as int16 where the bound proven from the deployed
operands fits (`fused_conv.stream_bounds`), else int32: at published
widths the stem and stages 1-2 are int16 and stages 3-4 int32, larger
magnitudes keep int32, and nets that drive a stream to exactly its bound,
or sum-pool an int16 stream past 32,767, still give the oracle's votes.
"""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.paper_cnn import (BIREAL18_CIFAR10, CIFAR10_CONVNET,
                                     bireal_convs, deploy_cnn,
                                     reactnet_convs)
from repro.core import convnet
from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec, FoldedConvLayer
from repro.deploy import Deployment
from repro.kernels import fused_conv, ref
from repro.serve.picbnn import BatchingPolicy, PicBnnServer
from repro.spec import InferenceSpec

ENC = InputEncoding("thermometer", 8)
NETS = {
    # Bi-Real-18's pattern cut to 16x16x3, 8/16/32/64 channels and one
    # basic block (two convs) a stage; the global pool sums 2x2 maps
    "bireal-16": CNNConfig(
        side=16, channels=3, encoding=ENC,
        conv=bireal_convs((8, 16, 32, 64), 2, 2), hidden=(), n_classes=10),
    # a stream that a plain pooled conv and an FC hidden layer read
    "mixed-8": CNNConfig(
        side=8, channels=3, encoding=InputEncoding("thermometer", 2),
        conv=(ConvSpec(3, 16, 1, "same", residual=True),
              ConvSpec(3, 16, 1, "same", residual=True,
                       shortcut="identity"),
              ConvSpec(3, 32, 2, "same", residual=True, shortcut="down"),
              ConvSpec(3, 32, 1, "same", 2)),
        hidden=(24,), n_classes=5),
    # ReActNet-A's pattern cut to 16x16x3 and 8/16/16/32 channels: a
    # stride-2 stem, a block whose 1x1 doubles the width ("dup"), one of
    # identities, and a stride-2 block ("avg") whose 1x1 doubles it again
    "reactnet-16": CNNConfig(
        side=16, channels=3, encoding=ENC,
        conv=reactnet_convs(8, ((16, 1), (16, 1), (32, 2)), 4), hidden=(),
        n_classes=10),
}
SHORTCUTS = {"bireal-16": {"none", "identity", "down"},
             "mixed-8": {"none", "identity", "down"},
             "reactnet-16": {"none", "identity", "avg", "dup"}}


def _images(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (n, cfg.n_in)) / 255.0).astype(np.float32)


def _oracle(cfg, folded, head, x):
    return np.asarray(ref.conv_votes_ref(folded, head, x, cfg.encoding,
                                         cfg.side, cfg.channels))


def _deployment(name, seed, **kw):
    cfg = NETS[name]
    folded = convnet.random_folded_cnn(cfg, seed=seed)
    return cfg, folded, deploy_cnn(cfg, folded, min_bucket=8, **kw)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
@pytest.mark.parametrize("name", sorted(NETS))
def test_pipeline_votes_equal_oracle(name, seed):
    cfg, folded, dep = _deployment(name, seed)
    convs = folded[:len(cfg.conv)]
    assert {l.shortcut for l in convs
            if l.residual is not None} == SHORTCUTS[name]
    x = _images(cfg, 13, seed=seed % 97)
    want = _oracle(cfg, folded, dep.pipeline().head, x)
    got = np.asarray(dep.run(x, InferenceSpec()))
    np.testing.assert_array_equal(got, want)
    assert len(np.unique(got)) > 2  # the comparison has something to miss


def _first(shortcut):
    return lambda folded: next(j for j, l in enumerate(folded)
                               if getattr(l, "shortcut", None) == shortcut)


def _rprelu(layer, **kw):
    return dataclasses.replace(
        layer, rprelu=dataclasses.replace(layer.rprelu, **kw))


# (net, the layer changed, the change): each must move the votes
FIELD_CHANGES = {
    # half of a downsample layer's magnitudes raised to 4
    "magnitudes": ("bireal-16", _first("down"), lambda l: dataclasses.replace(
        l, residual=np.where(np.arange(l.c_out) < l.c_out // 2, 4,
                             l.residual))),
    # a downsample's filters negated
    "down-filters": ("bireal-16", _first("down"),
                     lambda l: dataclasses.replace(l, down=dataclasses.replace(
                         l.down, weights_pm1=-l.down.weights_pm1))),
    # RSign thresholds, RPReLU's shifts and slope of the "dup" 1x1
    "alpha": ("reactnet-16", _first("dup"),
              lambda l: dataclasses.replace(l, alpha=l.alpha + 60)),
    "gamma": ("reactnet-16", _first("dup"),
              lambda l: _rprelu(l, gamma=l.rprelu.gamma + 60)),
    "q": ("reactnet-16", _first("dup"),
          lambda l: _rprelu(l, q=8 - l.rprelu.q)),
    "zeta": ("reactnet-16", _first("dup"),
             lambda l: _rprelu(l, zeta=l.rprelu.zeta - 60)),
    # the shortcuts "avg" and "dup" dropped: the layer restarts the stream
    "avg": ("reactnet-16", _first("avg"),
            lambda l: dataclasses.replace(l, shortcut="none")),
    "dup": ("reactnet-16", _first("dup"),
            lambda l: dataclasses.replace(l, shortcut="none")),
    # a 1x1 residual conv on the main path, its filters negated
    "k1-filters": ("reactnet-16", _first("dup"),
                   lambda l: dataclasses.replace(
                       l, weights_pm1=-l.weights_pm1)),
}


@pytest.mark.parametrize("change", sorted(FIELD_CHANGES))
def test_every_residual_field_moves_the_votes(change):
    """Change one field of one layer: the oracle and the deployed path
    change alike."""
    name, pick, alter = FIELD_CHANGES[change]
    cfg, folded, dep = _deployment(name, 4)
    x = _images(cfg, 24, seed=5)
    base = np.asarray(dep.run(x, InferenceSpec()))
    i = pick(folded)
    changed = list(folded)
    changed[i] = alter(folded[i])
    d = deploy_cnn(cfg, changed, min_bucket=8)
    got = np.asarray(d.run(x, InferenceSpec()))
    np.testing.assert_array_equal(
        got, _oracle(cfg, changed, d.pipeline().head, x))
    assert (got != base).any()


def test_deployment_round_trip_keeps_the_residual_fields(tmp_path):
    cfg, folded, dep = _deployment("bireal-16", 6)
    back = Deployment.load(dep.save(tmp_path / "d"))
    for a, b in zip(dep.conv_layers, back.conv_layers):
        assert (a.shortcut, a.pool, a.stride) == (b.shortcut, b.pool, b.stride)
        np.testing.assert_array_equal(a.residual, b.residual)
        np.testing.assert_array_equal(a.weights_pm1, b.weights_pm1)
        assert (a.down is None) == (b.down is None)
        if a.down is not None:
            np.testing.assert_array_equal(a.down.weights_pm1,
                                          b.down.weights_pm1)
            np.testing.assert_array_equal(a.down.residual, b.down.residual)
            np.testing.assert_array_equal(a.down.c, b.down.c)
    x = _images(cfg, 9, seed=7)
    np.testing.assert_array_equal(np.asarray(back.run(x, InferenceSpec())),
                                  np.asarray(dep.run(x, InferenceSpec())))


def test_server_answers_a_residual_deployment_as_the_oracle(tmp_path):
    cfg, folded, dep = _deployment("bireal-16", 8, max_bucket=32)
    x = _images(cfg, 20, seed=9)
    srv = PicBnnServer(BatchingPolicy(max_batch=16, max_wait_us=200))
    srv.register("bireal", dep.save(tmp_path / "bireal"))
    with srv:
        res = srv.submit_many("bireal", x).results(timeout=120)
    np.testing.assert_array_equal(
        np.stack([r.votes for r in res]),
        _oracle(cfg, folded, dep.pipeline().head, x))


def _layer(c_out, c_in, **kw):
    rng = np.random.default_rng(c_out + c_in)
    k = kw.pop("k", 3)
    return FoldedConvLayer(
        weights_pm1=rng.choice([-1, 1], (c_out, k, k, c_in)).astype(np.int8),
        c=np.zeros(c_out, np.int64), **kw)


def _m(n):
    return np.ones(n, np.int64)


BAD_LAYERS = {
    "shortcut-without-residual": lambda: _layer(8, 8, shortcut="identity"),
    "magnitude-below-one": lambda: _layer(8, 8, residual=np.zeros(8)),
    "residual-with-pool-sign": lambda: _layer(
        8, 8, residual=_m(8), pool=2, pool_sign=np.ones(8, np.int8)),
    "down-without-its-conv": lambda: _layer(8, 8, residual=_m(8),
                                            shortcut="down"),
    "down-conv-of-other-width": lambda: _layer(
        16, 8, residual=_m(16), shortcut="down", stride=2,
        down=_layer(16, 4, k=1, residual=_m(16))),
    "unknown-shortcut": lambda: _layer(8, 8, residual=_m(8),
                                       shortcut="concat"),
}


@pytest.mark.parametrize("case", sorted(BAD_LAYERS))
def test_malformed_residual_layers_are_refused(case):
    with pytest.raises(ValueError):
        BAD_LAYERS[case]()


BAD_STACKS = {
    # the first layer has no stream to add
    "identity-first": [dict(c_out=8, c_in=24, residual=_m(8),
                            shortcut="identity", padding="same")],
    # a plain layer ends the stream
    "identity-after-plain": [
        dict(c_out=8, c_in=24, padding="same"),
        dict(c_out=8, c_in=8, residual=_m(8), shortcut="identity",
             padding="same")],
    # the identity would add an 8x8 stream to a 4x4 output
    "identity-across-stride": [
        dict(c_out=8, c_in=24, residual=_m(8), padding="same"),
        dict(c_out=8, c_in=8, residual=_m(8), shortcut="identity",
             padding="same", stride=2)],
    # VALID padding shrinks the output under the stream
    "identity-valid": [
        dict(c_out=8, c_in=24, residual=_m(8), padding="same"),
        dict(c_out=8, c_in=8, residual=_m(8), shortcut="identity")],
}


@pytest.mark.parametrize("case", sorted(BAD_STACKS))
def test_shortcuts_without_a_stream_of_their_shape_are_refused(case):
    layers = [_layer(**kw) for kw in BAD_STACKS[case]]
    with pytest.raises(ValueError, match="shortcut"):
        fused_conv.conv_metas_for(layers, 8)


def test_each_vote_dispatch_counts_the_stream_its_program_writes():
    cfg, _, dep = _deployment("bireal-16", 10)
    pipe = dep.pipeline()
    # the stem and stage 1 write 3 maps of 16^2 x 8, then stages 2-4
    # 2 each of 8^2 x 16, 4^2 x 32, 2^2 x 64 elements, 4 B each
    per_row = 4 * (3 * 16 ** 2 * 8 + 2 * (8 ** 2 * 16 + 4 ** 2 * 32
                                          + 2 ** 2 * 64))
    assert pipe.stream_bytes_per_row == per_row
    x = _images(cfg, 13, seed=11)  # a 16-row bucket
    jax.block_until_ready(dep.run(x, InferenceSpec()))
    before = obs.counters()
    jax.block_until_ready(dep.run(x, InferenceSpec()))
    after = obs.counters()
    assert after["kernel.stream_bytes"] - before["kernel.stream_bytes"] \
        == 16 * per_row
    assert after["kernel.rows"] - before["kernel.rows"] == 13
    plain = deploy_cnn(CIFAR10_CONVNET, convnet.random_folded_cnn(
        CIFAR10_CONVNET), min_bucket=8)
    assert plain.pipeline().stream_bytes_per_row == 0


def test_bireal18_geometry_at_published_widths():
    cfg = BIREAL18_CIFAR10
    assert [s.c_out for s in cfg.conv] == [64] * 5 + [128] * 4 \
        + [256] * 4 + [512] * 4
    assert [s.shortcut for s in cfg.conv].count("down") == 3
    assert cfg.feature_sides()[-2:] == [4, 1] and cfg.fc_sizes == (512, 10)
    folded = convnet.random_folded_cnn(cfg, seed=1)
    metas = fused_conv.conv_metas_for(folded[:-1], cfg.side)
    assert fused_conv.stream_bytes_per_row(metas) == 4 * 557_056
    bits = sum(l.weights_pm1.size + (0 if l.down is None
                                     else l.down.weights_pm1.size)
               for l in folded[:-1])
    assert bits + folded[-1].weights_pm1.size == 11_176_448


def test_training_refuses_a_residual_config():
    cfg = NETS["bireal-16"]
    with pytest.raises(ValueError, match="plain conv chains"):
        convnet.fold_cnn({}, cfg)
    with pytest.raises(ValueError, match="plain conv chains"):
        convnet.cnn_forward({}, np.zeros((1, cfg.n_in), np.float32), cfg)


def _at_extremes(layer, m: int, c: int):
    """`layer` (and its downsample conv) with every magnitude m and one
    channel's C at -c, so its term is exactly k^2 * c_in * m + c."""
    cs = np.asarray(layer.c, np.int64).copy()
    cs[0] = -c
    down = None if layer.down is None else _at_extremes(layer.down, m, c)
    return dataclasses.replace(layer, residual=np.full(layer.c_out, m),
                               c=cs, down=down)


STAGE = {64: 0, 128: 1, 256: 2, 512: 3}  # Bi-Real-18's stage of a width
STREAM_CASES = {
    # m <= 4, |C| <= 40: stem + stage 1 904 + 4 * 2,344; stage 2 2,344 +
    # 296 + 3 * 4,648; stage 3 4,648 + 552 + 3 * 9,256; stage 4 9,256 +
    # 1,064 + 3 * 18,472, times the global pool's 16 sums
    "published": ((4, 4, 4, 4), (10_280, 16_584, 32_968, 16 * 65_736),
                  ("int16", "int16", "int32", "int32"),
                  (327_680 + 131_072) * 2 + 98_304 * 4),
    # m up to 8 in stage 2: 4,648 + 552 + 3 * 9,256 leaves int16
    "m8-stage2": ((4, 8, 4, 4), (10_280, 32_968, 32_968, 16 * 65_736),
                  ("int16", "int32", "int32", "int32"),
                  327_680 * 2 + (131_072 + 98_304) * 4),
}


@pytest.mark.parametrize("case", sorted(STREAM_CASES))
def test_the_stream_is_stored_at_the_width_its_proven_bound_allows(case):
    m, bounds, types, stored = STREAM_CASES[case]
    cfg = BIREAL18_CIFAR10
    drawn = convnet.random_folded_cnn(cfg, seed=1)[:len(cfg.conv)]
    layers = [_at_extremes(l, m[STAGE[l.c_out]], 40) for l in drawn]
    stage = [STAGE[l.c_out] for l in layers]
    got = fused_conv.stream_bounds(layers)
    metas = fused_conv.conv_metas_for(layers, cfg.side)
    for i in range(4):
        assert {b for b, s in zip(got, stage) if s == i} == {bounds[i]}
        assert {str(mt.stream_dtype) for mt, s in zip(metas, stage)
                if s == i} == {types[i]}
    assert fused_conv.stream_stored_bytes_per_row(metas) == stored
    assert fused_conv.stream_bytes_per_row(metas) == 4 * 557_056
    if case == "published":  # a draw of m in 1..4, |C| <= 24: same types
        assert [mt.stream_dtype for mt in fused_conv.conv_metas_for(
            drawn, cfg.side)] == [mt.stream_dtype for mt in metas]


# a stem and an identity layer whose stream's interior reaches its bound,
# then a stride-2 downsample layer whose 4 x 4 x 16 map the head reads
EDGE = CNNConfig(
    side=8, channels=1, encoding=InputEncoding("thermometer", 4),
    conv=(ConvSpec(3, 16, 1, "same", residual=True),
          ConvSpec(3, 16, 1, "same", residual=True, shortcut="identity"),
          ConvSpec(3, 16, 2, "same", residual=True, shortcut="down")),
    hidden=(), n_classes=10)


def _edge_layers(bound: int, polarity: int):
    """EDGE's conv layers at m = 127, the most an int8 filter holds.  On
    all-+1 input the stem's rows are all `polarity` and the identity
    layer's all +1, every C of the sign `polarity`, so the stream's
    interior is polarity * bound, the bound `stream_bounds` proves.  The
    downsample's 3x3 rows sum to 0 over each tap and its 1x1 rows are all
    +1, so each of its outputs is 16 * sign(the 2x2 sum of the stream):
    a sum of four values of up to 32,767 that an int16 sum would wrap."""
    full = functools.partial(np.full, dtype=np.int64)
    m = 127
    t_stem = 9 * 4 * m + 40
    c_ident = bound - t_stem - 9 * 16 * m
    stem = FoldedConvLayer(
        weights_pm1=np.full((16, 3, 3, 4), polarity, np.int8),
        c=full(16, polarity * 40), padding="same", residual=full(16, m))
    ident = FoldedConvLayer(
        weights_pm1=np.ones((16, 3, 3, 16), np.int8),
        c=full(16, polarity * c_ident), padding="same",
        residual=full(16, m), shortcut="identity")
    balanced = np.broadcast_to(np.resize(np.int8([1, -1]), 16),
                               (16, 3, 3, 16)).copy()
    down = FoldedConvLayer(weights_pm1=np.ones((16, 1, 1, 16), np.int8),
                           c=full(16, 0), residual=full(16, 1))
    last = FoldedConvLayer(
        weights_pm1=balanced, c=full(16, 0), stride=2, padding="same",
        residual=full(16, 1), shortcut="down", down=down)
    return [stem, ident, last]


@pytest.mark.parametrize("polarity", [1, -1], ids=["pos", "neg"])
@pytest.mark.parametrize("bound, stored", [(32_767, "int16"),
                                           (32_768, "int32")])
def test_a_stream_at_its_bound_and_its_downsample_equal_the_oracle(
        bound, stored, polarity):
    convs = _edge_layers(bound, polarity)
    assert fused_conv.stream_bounds(convs)[:2] == (bound, bound)
    metas = fused_conv.conv_metas_for(convs, EDGE.side)
    assert [str(m.stream_dtype) for m in metas] == [stored, stored, "int16"]
    folded = convs + convnet.random_folded_cnn(EDGE, seed=5)[3:]
    dep = deploy_cnn(EDGE, folded, min_bucket=8)
    x = np.ones((8, EDGE.n_in), np.float32)  # every thermometer bit fires
    want = _oracle(EDGE, folded, dep.pipeline().head, x)
    np.testing.assert_array_equal(np.asarray(dep.run(x, InferenceSpec())),
                                  want)
