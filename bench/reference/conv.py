"""Plain reference of a binary ConvNet configuration, in `jax.numpy` float32.

Written from the configuration file and BinaryNet's equations alone; it
imports nothing of the program under test.  A request row is a
[side, side, channels] image of [0, 1] pixels in HWC order.  Then:

    input    each channel value x -> `width` thermometer bits, bit t
             firing iff x >= (t + 1) / (width + 1), as input channel
             c * width + t, mapped to ±1
    conv     y = the k x k conv of the ±1 map with ±1 filters, zero
             padded ("same", as XLA pads it) or not ("valid"); a pooled
             layer max-pools y (pool x pool, stride pool); then
             sign(s * y + C), `>= 0` is +1, with s = ±1 the sign of the
             channel's folded BN scale and C an integer
    flatten  HWC
    FC, head as `bench/reference/bnn.py` (`fc_hd`, Algorithm 1)

Every conv and dot runs at `Precision.HIGHEST`, so float32 holds each
integer sum exactly.  `rnd` rounds the input and every conv sum to a
lower precision for the control; the reference itself passes `None`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import bnn

HIGHEST = jax.lax.Precision.HIGHEST
SUB_BLOCK = 256  # rows per reference step, to bound its memory


def thermometer_pm1(x, width: int):
    """[..., C] pixels -> ±1 [..., C * width], channel-major."""
    t = (jnp.arange(width, dtype=jnp.float32) + 1.0) / (width + 1.0)
    bits = x[..., None] >= t
    return jnp.where(bits, 1.0, -1.0).reshape(*x.shape[:-1], -1)


def conv_layer(h, w, s, c, spec: dict, rnd=bnn._keep):
    """One binary conv layer: ±1 maps [B, H, W, Ci] -> ±1 [B, H', W', Co].

    w: ±1 float32 HWIO filters [k, k, Ci, Co]; s: ±1 [Co]; c: [Co].
    """
    y = jax.lax.conv_general_dilated(
        h, w, (spec["stride"], spec["stride"]), spec["padding"].upper(),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    y = rnd(y)
    if spec["pool"] > 1:
        win = (1, spec["pool"], spec["pool"], 1)
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, win, win,
                                  "VALID")
    return bnn.sign(s * y + c)


def conv_hd(x, cfg: dict, conv, fc, rnd=bnn._keep):
    """Head Hamming distances [B, C] of pixel rows `x` [B, n_in].

    conv : [(w HWIO, s, c)] per conv layer of `cfg["conv"]`
    fc   : [(W ±1 [out, in], C [out])] hidden layers then the output
           layer, whose C is already as the bias cells hold it
    Rows run in sub-blocks of SUB_BLOCK where the batch divides into
    them.
    """
    side, ch = cfg["side"], cfg["channels"]
    width = cfg["encoding"]["width"]

    def block(xb):
        h = thermometer_pm1(rnd(xb).reshape(-1, side, side, ch), width)
        for (w, s, c), spec in zip(conv, cfg["conv"]):
            h = conv_layer(h, w, s, c, spec, rnd)
        return bnn.fc_hd(h.reshape(h.shape[0], -1), fc, cfg["bias_cells"],
                         rnd)

    n = x.shape[0]
    if n > SUB_BLOCK and n % SUB_BLOCK == 0:
        out = jax.lax.map(block, x.reshape(n // SUB_BLOCK, SUB_BLOCK, -1))
        return out.reshape(n, -1)
    return block(x)
