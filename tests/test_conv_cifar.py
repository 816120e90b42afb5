"""BinaryNet-style CNNs: RGB thermometer input, SAME padding, OR/AND pool.

The deployed path (`Deployment` -> `CompiledPipeline.run`, and the
server) must give the unpacked oracle's votes exactly
(`kernels.ref.conv_layer_ref`: sign(s * maxpool(conv) + C) on the rows
the fold did not negate) on reduced nets with both pool polarities,
SAME and VALID padding, and a map whose every output position touches
the padding; and the fold must turn a pooled float layer into that
deployment form.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import CIFAR10_CONVNET, deploy_cnn
from repro.core import convnet
from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec
from repro.deploy import Deployment
from repro.kernels import fused_conv, ref
from repro.serve.picbnn import BatchingPolicy, PicBnnServer
from repro.spec import InferenceSpec

NETS = {
    # BinaryNet's pattern, cut to 8x8x3 and 32/64 channels
    "same-pool-8": CNNConfig(
        side=8, channels=3, encoding=InputEncoding("thermometer", 8),
        conv=(ConvSpec(3, 32, 1, "same"), ConvSpec(3, 32, 1, "same", 2),
              ConvSpec(3, 64, 1, "same"), ConvSpec(3, 64, 1, "same", 2)),
        hidden=(48,), n_classes=10),
    "valid-pool-10": CNNConfig(
        side=10, channels=3, encoding=InputEncoding("thermometer", 4),
        conv=(ConvSpec(3, 32, 1, "valid", 2), ConvSpec(3, 64, 1, "same")),
        hidden=(), n_classes=6),
    # 2x2 maps under a 3x3 SAME conv: every output position is a border
    # position, each missing a different row and column of taps
    "all-border-2": CNNConfig(
        side=2, channels=3, encoding=InputEncoding("thermometer", 2),
        conv=(ConvSpec(3, 32, 1, "same"), ConvSpec(3, 64, 1, "same", 2)),
        hidden=(32,), n_classes=5),
}


def _images(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 256, (n, cfg.n_in)) / 255.0).astype(np.float32)


def _oracle(cfg, folded, head, x):
    return np.asarray(ref.conv_votes_ref(folded, head, x, cfg.encoding,
                                         cfg.side, cfg.channels))


@pytest.mark.parametrize("name", sorted(NETS))
def test_pipeline_votes_equal_oracle(name):
    cfg = NETS[name]
    folded = convnet.random_folded_cnn(cfg, seed=sum(map(ord, name)))
    pooled = [l for l in folded[:len(cfg.conv)] if l.pool > 1]
    assert all(set(np.unique(l.pool_sign)) == {-1, 1} for l in pooled)
    dep = deploy_cnn(cfg, folded, min_bucket=8)
    x = _images(cfg, 13, seed=len(name))
    want = _oracle(cfg, folded, dep.pipeline().head, x)
    got = np.asarray(dep.run(x, InferenceSpec()))
    np.testing.assert_array_equal(got, want)
    assert len(np.unique(got)) > 2  # the comparison has something to miss


def test_pool_polarity_is_or_and_and():
    """A pooled channel ORs its window's sign bits for s = +1 and ANDs
    them for s = -1: flipping one channel's polarity changes the votes
    the oracle gives exactly as it changes the deployed path's."""
    cfg = NETS["same-pool-8"]
    folded = convnet.random_folded_cnn(cfg, seed=4)
    sign = folded[1].pool_sign.copy()
    sign[: len(sign) // 2] *= -1
    flipped = list(folded)
    flipped[1] = dataclasses.replace(folded[1], pool_sign=sign)
    x = _images(cfg, 16, seed=5)
    out = []
    for f in (folded, flipped):
        dep = deploy_cnn(cfg, f, min_bucket=16)
        got = np.asarray(dep.run(x, InferenceSpec()))
        np.testing.assert_array_equal(
            got, _oracle(cfg, f, dep.pipeline().head, x))
        out.append(got)
    assert (out[0] != out[1]).any()


def test_rgb_thermometer_bit_order():
    """Channel c's code bit t is input channel c * width + t."""
    enc = InputEncoding("thermometer", 8)
    px = np.array([0.0, 0.5, 1.0], np.float32)  # R, G, B of one pixel
    bits = np.asarray(enc.encode_image_bits(px.reshape(1, 1, 1, 3)))
    fill = [0, 4, 8]  # levels (t + 1) / 9 at or below each value
    want = np.concatenate([[1] * f + [0] * (8 - f) for f in fill])
    np.testing.assert_array_equal(bits.reshape(-1), want)
    np.testing.assert_array_equal(
        np.asarray(enc.encode_image_pm1(px.reshape(1, 1, 1, 3))).reshape(-1),
        2.0 * want - 1)
    # the vote program's input maps are that order, as ±1 int8 per pixel
    cfg = NETS["all-border-2"]
    img = _images(cfg, 3, seed=2)
    maps = np.asarray(fused_conv.encode_input(
        jnp.asarray(img), cfg.side, cfg.channels, cfg.encoding))
    assert maps.dtype == np.int8
    assert maps.shape == (3, cfg.side, cfg.side, 3 * cfg.encoding.width)
    code = np.asarray(cfg.encoding.encode_image_bits(
        img.reshape(3, cfg.side, cfg.side, cfg.channels)))
    np.testing.assert_array_equal(maps, 2 * code.astype(np.int8) - 1)
    one = np.asarray(fused_conv.encode_input(
        jnp.asarray(px.reshape(1, 3)), 1, 3, enc)).reshape(-1)
    np.testing.assert_array_equal(one, 2 * want - 1)


def test_deployment_round_trip_keeps_the_new_fields(tmp_path):
    cfg = NETS["same-pool-8"]
    folded = convnet.random_folded_cnn(cfg, seed=6)
    dep = deploy_cnn(cfg, folded, min_bucket=8)
    back = Deployment.load(dep.save(tmp_path / "d"))
    assert (back.image_side, back.image_channels) == (8, 3)
    assert back.image_encoding == cfg.encoding
    for a, b in zip(dep.conv_layers, back.conv_layers):
        assert (a.padding, a.pool, a.stride) == (b.padding, b.pool, b.stride)
        if a.pool_sign is None:
            assert b.pool_sign is None
        else:
            np.testing.assert_array_equal(a.pool_sign, b.pool_sign)
        np.testing.assert_array_equal(a.weights_pm1, b.weights_pm1)
    x = _images(cfg, 9, seed=7)
    np.testing.assert_array_equal(np.asarray(back.run(x, InferenceSpec())),
                                  np.asarray(dep.run(x, InferenceSpec())))


def test_server_answers_a_three_channel_deployment_as_run(tmp_path):
    cfg = NETS["same-pool-8"]
    dep = deploy_cnn(cfg, convnet.random_folded_cnn(cfg, seed=8),
                     min_bucket=8, max_bucket=32)
    assert dep.pipeline().n_in == 8 * 8 * 3
    x = _images(cfg, 20, seed=9)
    direct = np.asarray(dep.run(x, InferenceSpec()))
    srv = PicBnnServer(BatchingPolicy(max_batch=16, max_wait_us=200))
    srv.register("rgb", dep.save(tmp_path / "rgb"))
    with srv:
        res = srv.submit_many("rgb", x).results(timeout=60)
    np.testing.assert_array_equal(np.stack([r.votes for r in res]), direct)


def _bn(gamma, mean):
    n = len(gamma)
    return {"gamma": jnp.asarray(gamma, jnp.float32),
            "beta": jnp.zeros((n,), jnp.float32),
            "mean": jnp.asarray(mean, jnp.float32),
            "var": jnp.full((n,), 1.0 - 1e-5, jnp.float32)}


def test_fold_of_a_pooled_layer_gives_or_and_polarity():
    """conv -> max-pool -> BN -> sign of a float layer equals the folded
    layer, for BN scales of both signs.  Odd integer means keep every
    even dot off the threshold, so the fold's rounding is exact."""
    cfg = CNNConfig(side=6, channels=3, encoding=InputEncoding("thermometer",
                                                               2),
                    conv=(ConvSpec(3, 8, 1, "same", 2),), hidden=(),
                    n_classes=3)
    rng = np.random.default_rng(3)
    gamma = np.array([1, -1] * 4, np.float32)
    mean = 2 * rng.integers(-4, 4, 8) + 1
    params = convnet.init_cnn_params(jax.random.PRNGKey(1), cfg)
    params["conv"][0].update(_bn(gamma, mean))
    folded = convnet.fold_cnn(params, cfg)
    layer = folded[0]
    np.testing.assert_array_equal(layer.pool_sign, np.sign(gamma))
    x = _images(cfg, 11, seed=4)
    h = cfg.encoding.encode_image_pm1(jnp.asarray(x).reshape(11, 6, 6, 3))
    y = jax.lax.conv_general_dilated(
        h, jnp.sign(params["conv"][0]["w"]), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    want = jnp.where(gamma * (y - mean) >= 0, 1.0, -1.0)
    np.testing.assert_array_equal(np.asarray(ref.conv_layer_ref(h, layer)),
                                  np.asarray(want))


def test_cifar10_convnet_geometry():
    cfg = CIFAR10_CONVNET
    assert cfg.n_in == 32 * 32 * 3
    assert cfg.feature_channels()[0] == 24
    assert cfg.feature_sides() == [32, 32, 16, 16, 8, 8, 4]
    assert [s.conv_side(n) for s, n in zip(cfg.conv, cfg.feature_sides())] \
        == [32, 32, 16, 16, 8, 8]
    assert cfg.fc_sizes == (8192, 1024, 1024, 10)
    cost = convnet.cnn_inference_cost(cfg)
    assert cost is not None
    with pytest.raises(ValueError):
        ConvSpec(3, 8, padding="full")
