"""End-to-end-binary CNN workload: bit-exactness vs the unpacked oracle.

The correctness bar for kernels/fused_conv.py and the conv path of
repro/pipeline.py: the deployed flow (one int8 program on every
backend) must be bit-identical to `kernels.ref.conv_votes_ref` — the ±1
float oracle that encodes raw pixels through the binary input layer,
runs every conv/FC layer as sign(dot + C), and votes the head — across
multiple input sizes, strides, channel alignments, both input forms (a
host batch is staged, a device batch passes through), and the
silicon-mode specs.
"""

import jax
import numpy as np
import pytest

from repro.configs.paper_cnn import HG_CNN, MNIST_CNN, build_cnn_pipeline
from repro.core import convnet
from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec
from repro.core.device_model import NOISELESS, SILICON
from repro.kernels import ref
from repro.spec import InferenceSpec

# Two input sizes (the acceptance bar asks for >= 2), plus a config with
# channel counts that fill no whole word, and a conv->head-direct net
# with no FC hidden layer.
CONFIGS = {
    "mnist-28": CNNConfig(
        side=28, encoding=InputEncoding("thermometer", 8),
        conv=(ConvSpec(3, 32, 2), ConvSpec(3, 32, 2)), hidden=(128,),
        n_classes=10,
    ),
    "hg-64": CNNConfig(
        side=64, encoding=InputEncoding("thermometer", 4),
        conv=(ConvSpec(3, 32, 2), ConvSpec(3, 32, 2)), hidden=(128,),
        n_classes=20,
    ),
    "unaligned-12": CNNConfig(
        side=12, encoding=InputEncoding("thermometer", 3),
        conv=(ConvSpec(3, 24, 2), ConvSpec(3, 20, 1)), hidden=(48,),
        n_classes=7,
    ),
    "head-direct-10": CNNConfig(
        side=10, encoding=InputEncoding("thermometer", 2),
        conv=(ConvSpec(3, 32, 2),), hidden=(), n_classes=5,
    ),
}


def _images(cfg, n, seed=1):
    return np.random.default_rng(seed).random((n, cfg.n_in)).astype(
        np.float32
    )


def _oracle(cfg, folded, head, x):
    return np.asarray(
        ref.conv_votes_ref(folded, head, x, cfg.encoding, cfg.side)
    )


#: how a caller hands a batch to `run`: a host NumPy array, or a
#: `jax.Array` already committed to the device
INPUTS = ["host", "device"]


def _as_input(x, form):
    return x if form == "host" else jax.device_put(x, jax.devices()[0])


def _votes(pipe, x, key=None):
    """The noise-off votes, or one batch-level draw under `key`."""
    if key is None:
        return np.asarray(pipe.run(x, InferenceSpec()))
    return np.asarray(pipe.run(x, InferenceSpec(noise="batch"), key=key))


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("form", INPUTS)
def test_conv_pipeline_bit_exact_vs_oracle(name, form):
    cfg = CONFIGS[name]
    folded = convnet.random_folded_cnn(cfg, seed=sum(map(ord, name)))
    pipe = build_cnn_pipeline(cfg, folded)
    x_np = _images(cfg, 6 if cfg.side >= 64 else 11)
    x = _as_input(x_np, form)
    want = _oracle(cfg, folded, pipe.head, x_np)
    np.testing.assert_array_equal(_votes(pipe, x), want)
    np.testing.assert_array_equal(
        np.asarray(pipe.run(x, InferenceSpec(reduction="argmax"))),
        want.argmax(-1),
    )


@pytest.mark.parametrize("form", INPUTS)
def test_conv_noiseless_limit_bit_exact(form):
    """sigma -> 0: every silicon spec equals the oracle."""
    cfg = CONFIGS["unaligned-12"]
    folded = convnet.random_folded_cnn(cfg, seed=3)
    pipe = build_cnn_pipeline(cfg, folded, noise=NOISELESS)
    x_np = _images(cfg, 9, seed=2)
    x = _as_input(x_np, form)
    want = _oracle(cfg, folded, pipe.head, x_np)
    key = jax.random.PRNGKey(7)
    np.testing.assert_array_equal(_votes(pipe, x, key), want)
    mc = np.asarray(pipe.run(x, InferenceSpec(noise="batch", mc_samples=3),
                             key=key))
    np.testing.assert_array_equal(mc, np.broadcast_to(want, mc.shape))
    cum = np.asarray(pipe.run(x, InferenceSpec(noise="batch",
                                               cumulative=True), key=key))
    np.testing.assert_array_equal(cum[-1], want)
    keys = jax.random.split(key, x.shape[0])
    np.testing.assert_array_equal(
        np.asarray(pipe.run(x, InferenceSpec(noise="per_request"),
                            keys=keys)), want)


@pytest.mark.parametrize("form", INPUTS)
def test_conv_silicon_impls_agree(form):
    """Same key => the batch-draw votes equal the last pass of the
    cumulative batch-draw spec (sampling happens outside the vote
    producer), and the draw actually perturbs."""
    cfg = CONFIGS["unaligned-12"]
    folded = convnet.random_folded_cnn(cfg, seed=5)
    pipe = build_cnn_pipeline(cfg, folded, noise=SILICON)
    # batch == bucket: shared sample shapes
    x = _as_input(_images(cfg, 64, seed=3), form)
    key = jax.random.PRNGKey(5)
    got = _votes(pipe, x, key)
    assert (got != _votes(pipe, x)).any()
    cum = pipe.run(x, InferenceSpec(noise="batch", cumulative=True), key=key)
    np.testing.assert_array_equal(got, np.asarray(cum)[-1])


def test_conv_batch_bucketing_invariance():
    cfg = CONFIGS["unaligned-12"]
    folded = convnet.random_folded_cnn(cfg, seed=9)
    pipe = build_cnn_pipeline(cfg, folded, min_bucket=8)
    x = _images(cfg, 21, seed=4)
    full = _votes(pipe, x)
    for b in (1, 7, 8, 9, 21):
        np.testing.assert_array_equal(_votes(pipe, x[:b]), full[:b])


def test_fold_cnn_smoke_trained_shapes_and_parity():
    """fold_cnn emits dead-zone-free constants and oracle-consistent
    layers for a (briefly) trained model."""
    cfg = CNNConfig(
        side=12, encoding=InputEncoding("thermometer", 2),
        conv=(ConvSpec(3, 8, 2),), hidden=(16,), n_classes=4,
    )
    rng = np.random.default_rng(0)
    tx = rng.random((64, cfg.n_in)).astype(np.float32)
    ty = rng.integers(0, cfg.n_classes, 64)
    params = convnet.train_cnn(jax.random.PRNGKey(0), cfg, tx, ty,
                               epochs=1, batch=32)
    folded = convnet.fold_cnn(params, cfg)
    assert isinstance(folded[0], convnet.FoldedConvLayer)
    assert folded[0].weights_pm1.shape == (8, 3, 3, 2)
    for layer in folded:
        n_bits = (layer.n_bits
                  if isinstance(layer, convnet.FoldedConvLayer)
                  else layer.n_in)
        assert ((layer.c + n_bits) % 2 == 1).all()
        assert (np.abs(layer.c) <= cfg.bias_cells).all()
    pipe = build_cnn_pipeline(cfg, folded)
    x = _images(cfg, 5, seed=6)
    np.testing.assert_array_equal(
        _votes(pipe, x), _oracle(cfg, folded, pipe.head, x)
    )


def test_train_cnn_clips_only_latent_weights():
    """BinaryConnect clipping applies to the latent weights ONLY: BN
    running stats must track real batch statistics (a conv pre-activation
    variance is ~n_bits, far above 1 — clipping it to [-1, 1] corrupts
    every eval/fold that consumes the stats)."""
    cfg = CNNConfig(
        side=12, encoding=InputEncoding("thermometer", 4),
        conv=(ConvSpec(3, 8, 2),), hidden=(), n_classes=4,
    )
    rng = np.random.default_rng(1)
    tx = rng.random((256, cfg.n_in)).astype(np.float32)
    ty = rng.integers(0, cfg.n_classes, 256)
    params = convnet.train_cnn(jax.random.PRNGKey(0), cfg, tx, ty,
                               epochs=2, batch=64)
    var = np.asarray(params["conv"][0]["var"])
    assert var.max() > 1.5, var  # 36-bit dot variance; 1.0 means clipped
    for layer in params["conv"] + params["fc"]:
        w = np.asarray(layer["w"])
        assert w.min() >= -1.0 and w.max() <= 1.0  # latents ARE clipped


def test_compile_pipeline_conv_validation():
    cfg = CONFIGS["head-direct-10"]
    folded = convnet.random_folded_cnn(cfg, seed=1)
    from repro import pipeline
    from repro.core.ensemble import EnsembleConfig

    with pytest.raises(ValueError, match="image_side"):
        pipeline.compile_pipeline(folded, EnsembleConfig())
    with pytest.raises(ValueError, match="conv-only"):
        pipeline.compile_pipeline(folded[-1:], EnsembleConfig(),
                                  image_side=10)
    with pytest.raises(ValueError, match="prefix"):
        pipeline.compile_pipeline(
            [folded[-1], folded[0]], EnsembleConfig(), image_side=10
        )
    with pytest.raises(ValueError, match="encoding width"):
        pipeline.compile_pipeline(
            folded, EnsembleConfig(), image_side=10,
            image_encoding=InputEncoding("thermometer", 5),
        )
    # a head fed straight by a conv whose channels fill no whole word
    # runs too: the int8 path has no word-aligned flatten to keep
    odd = CNNConfig(side=10, encoding=InputEncoding("thermometer", 2),
                    conv=(ConvSpec(3, 24, 2),), hidden=(), n_classes=5)
    folded = convnet.random_folded_cnn(odd, seed=2)
    pipe = build_cnn_pipeline(odd, folded)
    x = _images(odd, 5, seed=7)
    np.testing.assert_array_equal(_votes(pipe, x),
                                  _oracle(odd, folded, pipe.head, x))
    other_head = convnet.random_folded_cnn(CONFIGS["head-direct-10"])[-1]
    with pytest.raises(ValueError, match="flattened conv"):
        pipeline.compile_pipeline([folded[0], other_head], EnsembleConfig(),
                                  image_side=10)


def test_cnn_configs_consistent():
    """Paper CNN configs: geometry chains and word-aligned flattens."""
    for cfg in (MNIST_CNN, HG_CNN):
        sides = cfg.feature_sides()
        assert sides[0] == cfg.side and len(sides) == len(cfg.conv) + 1
        assert cfg.flat_features == sides[-1] ** 2 * cfg.conv[-1].c_out
        assert cfg.conv[-1].c_out % 32 == 0  # word-aligned flatten
        assert cfg.fc_sizes[-1] == cfg.n_classes
    assert MNIST_CNN.flat_features == 6 * 6 * 32 == 1152
    assert HG_CNN.flat_features == 15 * 15 * 32 == 7200


def test_conv_served_bit_exact():
    """The CNN is servable day one: served noiseless and silicon-mode
    (per-request-key) results are bit-exact vs direct pipeline calls,
    however the batcher coalesces the stream."""
    from repro.serve.picbnn import BatchingPolicy, PicBnnServer

    cfg = CONFIGS["unaligned-12"]
    folded = convnet.random_folded_cnn(cfg, seed=11)
    pipe = build_cnn_pipeline(cfg, folded, min_bucket=8, max_bucket=32)
    pipe_si = build_cnn_pipeline(cfg, folded, min_bucket=8, max_bucket=32,
                                 noise=SILICON)
    x = _images(cfg, 24, seed=8)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(3), 24))
    direct = np.asarray(pipe.run(x, InferenceSpec(reduction="argmax")))
    direct_si = np.asarray(pipe_si.run(
        x, InferenceSpec(noise="per_request", reduction="argmax"),
        keys=keys))
    srv = PicBnnServer(BatchingPolicy(max_batch=32, max_wait_us=200))
    srv.register("cnn", pipe)
    srv.register("cnn-si", pipe_si)
    with srv:
        h = srv.submit_many("cnn", x)
        h_si = srv.submit_many("cnn-si", x, keys=keys)
        np.testing.assert_array_equal(h.wait_all(timeout=60), direct)
        np.testing.assert_array_equal(h_si.wait_all(timeout=60), direct_si)
