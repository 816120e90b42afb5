"""The CNN vote program's input: raw pixels, encoded inside the program.

A conv pipeline stages its float32 pixel rows and the vote program
makes the ±1 int8 input maps itself (`fused_conv.encode_input`), so no
`picbnn_pack` program runs for it.  The encode must be the deployment's
`InputEncoding` bit for bit, on the pixels where a code changes, and the
votes must equal both the unpacked float oracle
(`kernels.ref.conv_votes_ref`) and the channel-packed words path the
program took before (rebuilt here as an oracle: pack the code into words,
unpack them to ±1 int8, run the same layers).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.paper_cnn import deploy_cnn
from repro.core import binarize, convnet
from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec
from repro.core.device_model import SILICON
from repro.kernels import fused_conv, ref
from repro.spec import InferenceSpec

ENCODINGS = {
    "thermometer-8": InputEncoding("thermometer", 8),
    "bitplane-4": InputEncoding("bitplane", 4),
    "sign": InputEncoding("sign", 1),
}

NETS = {
    # BinaryNet's input: thermometer-8 of RGB, 24 channels in one word
    "rgb-thermometer-8": CNNConfig(
        side=8, channels=3, encoding=ENCODINGS["thermometer-8"],
        conv=(ConvSpec(3, 32, 1, "same"), ConvSpec(3, 32, 1, "same", 2)),
        hidden=(48,), n_classes=10),
    # 36 input channels: the words path needed two words a pixel
    "rgb-thermometer-12": CNNConfig(
        side=6, channels=3, encoding=InputEncoding("thermometer", 12),
        conv=(ConvSpec(3, 32, 1, "same", 2),), hidden=(), n_classes=6),
    "gray-bitplane-4": CNNConfig(
        side=10, channels=1, encoding=ENCODINGS["bitplane-4"],
        conv=(ConvSpec(3, 32, 2), ConvSpec(3, 20, 1)), hidden=(32,),
        n_classes=7),
    "rgb-sign": CNNConfig(
        side=6, channels=3, encoding=ENCODINGS["sign"],
        conv=(ConvSpec(3, 32, 1, "same", 2),), hidden=(24,), n_classes=5),
}


def _edge_pixels(width: int) -> np.ndarray:
    """Every thermometer level k/(width+1), k/255 for every k, 0, 1 and
    two values outside [0, 1]."""
    levels = np.arange(width + 2, dtype=np.float32) / np.float32(width + 1)
    grid = np.arange(256, dtype=np.float32) / np.float32(255)
    return np.concatenate([levels, grid, [0.0, 1.0, -0.1, 1.5]]).astype(
        np.float32)


@pytest.mark.parametrize("name", sorted(ENCODINGS))
def test_encode_input_is_the_encoding_in_pm1_int8(name):
    enc = ENCODINGS[name]
    px = _edge_pixels(8 if enc.kind == "thermometer" else enc.width)
    side, channels = 2, 3
    n = side * side * channels
    px = np.resize(px, (-(-px.size // n) * n,)).reshape(-1, n)
    img = px.reshape(-1, side, side, channels)
    want = 2 * np.asarray(enc.encode_image_bits(img)).astype(np.int8) - 1
    jitted = jax.jit(fused_conv.encode_input, static_argnums=(1, 2, 3))
    for encode in (fused_conv.encode_input, jitted):
        got = np.asarray(encode(jnp.asarray(px), side, channels, enc))
        assert got.dtype == np.int8
        assert got.shape == (len(px), side, side, channels * enc.width)
        np.testing.assert_array_equal(got, want)
    assert set(np.unique(want)) == {-1, 1}  # both values occur


def _words_path_hd(pipe, cfg, folded, x):
    """Head distances of the words path: the code packed into words and
    unpacked to ±1 int8, then the deployed layers."""
    img = jnp.asarray(x).reshape(-1, cfg.side, cfg.side, cfg.channels)
    c0 = cfg.channels * cfg.encoding.width
    words = binarize.pack_bits(cfg.encoding.encode_image_bits(img))
    h = 2 * binarize.unpack_bits(words, c0).astype(jnp.int8) - 1
    conv, fc, (head_w, offset) = pipe.weight_operands
    metas = fused_conv.conv_metas_for(folded[:len(cfg.conv)], cfg.side)
    for (w, c, s), m in zip(conv, metas):
        h = fused_conv.conv_layer(h, w, c, s, m)
    h = h.reshape(h.shape[0], -1)
    for w, c in fc:
        h = fused_conv.fc_layer(h, w, c)
    dot = jnp.dot(h, head_w, preferred_element_type=jnp.int32)
    return (h.shape[1] - dot) // 2 + offset


def _deployed(name):
    cfg = NETS[name]
    folded = convnet.random_folded_cnn(cfg, seed=sum(map(ord, name)))
    dep = deploy_cnn(cfg, folded, noise=SILICON, min_bucket=8)
    return cfg, folded, dep.pipeline()


@pytest.mark.parametrize("rows", [13, 16], ids=["padded", "full"])
@pytest.mark.parametrize("noise", ["off", "batch"])
@pytest.mark.parametrize("name", sorted(NETS))
def test_run_votes_equal_the_words_path_and_the_oracle(name, noise, rows):
    cfg, folded, pipe = _deployed(name)
    rng = np.random.default_rng(rows)
    x = (rng.integers(0, 256, (rows, cfg.n_in)) / 255.0).astype(np.float32)
    hd = _words_path_hd(pipe, cfg, folded, x)
    thr = pipe.head.thresholds
    if noise == "off":
        got = np.asarray(pipe.run(x, InferenceSpec()))
        want = np.asarray((hd[:, :, None] <= thr).astype(jnp.int32).sum(-1))
        np.testing.assert_array_equal(got, np.asarray(ref.conv_votes_ref(
            folded, pipe.head, x, cfg.encoding, cfg.side, cfg.channels)))
    else:  # one draw over the whole bucket, as the program samples it
        key = jax.random.PRNGKey(rows)
        got = np.asarray(pipe.run(x, InferenceSpec(noise="batch"), key=key))
        bucket = 16
        hd = jnp.pad(hd, ((0, bucket - rows), (0, 0))).astype(jnp.float32)
        t = pipe.physics.sample(key, batch_shape=(bucket,),
                                n_rows=pipe.n_classes)
        want = np.asarray((hd[None] <= t).astype(jnp.int32).sum(0))[:rows]
    assert got.shape == (rows, pipe.n_classes)
    np.testing.assert_array_equal(got, want)
    assert len(np.unique(got)) > 2  # the comparison has something to miss


def test_conv_pipeline_has_no_pack_program():
    _, _, pipe = _deployed("rgb-sign")
    assert pipe._pack_fn is None
    text = pipe.program(InferenceSpec()).lower(
        jax.ShapeDtypeStruct((8, pipe.n_in), jnp.float32),
        ops=pipe.weight_operands).as_text()
    assert "module @jit_picbnn_votes_off " in text


@pytest.mark.parametrize("staged", [False, True], ids=["host", "device"])
def test_conv_run_counts_no_device_pack(staged):
    cfg, _, pipe = _deployed("rgb-sign")
    x = np.full((16, cfg.n_in), 0.75, np.float32)
    if staged:
        x = jax.device_put(x)
    jax.block_until_ready(pipe.run(x, InferenceSpec()))
    before = obs.counters()
    for _ in range(3):
        jax.block_until_ready(pipe.run(x, InferenceSpec()))
    after = obs.counters()
    assert after.get("pack.device_calls", 0) == before.get(
        "pack.device_calls", 0)
    assert after["kernel.rows"] - before["kernel.rows"] == 48
