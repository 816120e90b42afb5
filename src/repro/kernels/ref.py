"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def binary_gemm_hd_ref(x_packed, w_packed) -> jax.Array:
    """Pairwise Hamming distance: [M, Kw] x [N, Kw] -> [M, N] int32."""
    xor = jax.lax.bitwise_xor(x_packed[:, None, :], w_packed[None, :, :])
    return jax.lax.population_count(xor).astype(jnp.int32).sum(-1)


def cam_vote_ref(q_packed, rows_packed, thresholds) -> jax.Array:
    """Fused multi-threshold vote: [B, C] int32."""
    hd = binary_gemm_hd_ref(q_packed, rows_packed)
    return (hd[:, :, None] <= thresholds.astype(jnp.int32)).sum(-1).astype(
        jnp.int32
    )


def bitlinear_ref(x, w, n_bits: int | None = None) -> jax.Array:
    """+-1-domain binary matmul oracle: y = x @ w with x,w in {-1,+1}.

    x: [..., K] float/int +-1;  w: [K, N] +-1.  Returns float32 [..., N].
    """
    return jnp.asarray(x, jnp.float32) @ jnp.asarray(w, jnp.float32)


def binary_conv2d_ref(x_pm1, w_pm1, stride: int = 1,
                      padding: str = "valid") -> jax.Array:
    """±1-domain conv oracle: the unpacked ground truth.

    x_pm1: [B, H, W, C] ±1 activations;  w_pm1: [O, K, K, C] ±1 filters
    (CAM-row layout, `convnet.FoldedConvLayer.weights_pm1`).  Returns
    float32 [B, OH, OW, O] dot products — each output position is the
    XNOR-popcount dot of its K*K*C patch against every filter row, with
    zeros at "same" padding positions.
    """
    from repro.core.convnet import conv_pads

    x = jnp.asarray(x_pm1, jnp.float32)
    w = jnp.asarray(w_pm1, jnp.float32)
    pads = conv_pads(x.shape[1], w.shape[1], stride, padding)
    # conv_general_dilated computes a true convolution-as-correlation
    # with HWIO kernels, so transpose the row layout [O,K,K,C]->[K,K,C,O]
    return jax.lax.conv_general_dilated(
        x, jnp.transpose(w, (1, 2, 3, 0)),
        window_strides=(stride, stride), padding=(pads, pads),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )


def conv_layer_ref(h, layer) -> jax.Array:
    """One `FoldedConvLayer` on ±1 maps, in BinaryNet's form.

    The fold negated the rows of channels whose BN scale sign s is -1;
    this undoes that, max-pools the conv output y of the original rows
    and applies sign(s * maxpool(y) + C) — not the OR/AND of sign bits
    the deployed path computes.
    """
    s = np.where(layer.pool_or, 1.0, -1.0).astype(np.float32)
    w = np.asarray(layer.weights_pm1, np.float32) * s[:, None, None, None]
    y = binary_conv2d_ref(h, w, layer.stride, layer.padding)
    if layer.pool > 1:
        win = (1, layer.pool, layer.pool, 1)
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, win, win,
                                  "VALID")
    return jnp.where(s * y + jnp.asarray(layer.c, jnp.float32) >= 0,
                     1.0, -1.0)


def _sum_pool_ref(x, p: int):
    win = (1, p, p, 1)
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, win, win, "VALID")


def stream_layer_ref(h, x, layer) -> jax.Array:
    """One residual `FoldedConvLayer` in float32: the stream after it.

    m * conv(h) + C, with m the layer's BN magnitudes and the rows (which
    hold the sign s) as given, plus the shortcut: the stream x itself, or
    the downsample conv of sign(sumpool(x)) over stride x stride
    windows; a pool sums the result.  Every value is an integer far
    below 2^24, so float32 holds it exactly.
    """
    def scaled(h, l, stride, padding):
        y = binary_conv2d_ref(h, l.weights_pm1, stride, padding)
        return (y * jnp.asarray(l.residual, jnp.float32)
                + jnp.asarray(l.c, jnp.float32))

    y = scaled(h, layer, layer.stride, layer.padding)
    if layer.shortcut == "identity":
        y = y + x
    elif layer.shortcut == "down":
        xd = jnp.where(_sum_pool_ref(x, layer.stride) >= 0, 1.0, -1.0)
        y = y + scaled(xd, layer.down, 1, "valid")
    return y if layer.pool == 1 else _sum_pool_ref(y, layer.pool)


def conv_votes_ref(folded, head, x01, encoding, side: int,
                   channels: int = 1) -> jax.Array:
    """Unpacked end-to-end-binary CNN oracle: raw pixels -> vote counts.

    The ground truth for `kernels/fused_conv.py` and the conv pipeline:
    encode [0,1] pixels [B, side*side*channels] (HWC) through the binary
    input layer, run every FoldedConvLayer in ±1 floats
    (`conv_layer_ref`; a residual one with `stream_layer_ref`, the next
    layer reading the sign of its stream), flatten NHWC, run the folded
    FC hidden layers as sign(Wx + C), and vote the head with
    `ensemble.votes_fused`.  Bit-exactness of the deployed path against
    this oracle is asserted in tests/test_conv.py,
    tests/test_conv_cifar.py and tests/test_conv_resnet.py.
    """
    from repro.core.convnet import FoldedConvLayer
    from repro.core.ensemble import votes_fused

    b = jnp.asarray(x01).shape[0]
    h = encoding.encode_image_pm1(
        jnp.asarray(x01).reshape(b, side, side, channels)
    )
    flat, x = None, None
    for layer in folded[:-1]:
        if isinstance(layer, FoldedConvLayer) and layer.residual is not None:
            x = stream_layer_ref(h, x, layer)
            h = jnp.where(x >= 0, 1.0, -1.0)
        elif isinstance(layer, FoldedConvLayer):
            h = conv_layer_ref(h, layer)
        else:
            if flat is None:
                h, flat = h.reshape(b, -1), True
            y = h @ jnp.asarray(layer.weights_pm1.T, jnp.float32)
            h = jnp.where(y + jnp.asarray(layer.c, jnp.float32) >= 0,
                          1.0, -1.0)
    if flat is None:
        h = h.reshape(b, -1)
    return votes_fused(head, h)
