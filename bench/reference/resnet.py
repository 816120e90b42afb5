"""Plain reference of a binary ResNet with Bi-Real shortcuts, in `jax.numpy`
float32.

Written from the configuration file and Bi-Real Net's equations alone
(arXiv:1808.00278 Sec. 3.1); it imports nothing of the program under
test.  A request row is a [side, side, channels] image of [0, 1] pixels
in HWC order.  Every BN is integer, a = s * m per channel plus C, and
sign(v) is +1 for v >= 0.  Then:

    input    the thermometer code of `bench/reference/conv.py`, ±1
    stem     x_0 = a_0 * conv3x3(input) + C_0, zero padded ("SAME", as
             XLA pads it), no sign: the start of the residual stream
    convs    x_{l+1} = a_l * conv3x3_stride(sign(x_l)) + C_l + sc_l(x_l)
             with sc(x) = x, or for the first conv of a stage with
             stride 2 the downsample sc(x) = a_d * conv1x1(sign(
             sumpool2x2(x))) + C_d (sign(sumpool) is the sign of
             Bi-Real's AvgPool)
    head     h = sign(sum of x_L over its global_pool x global_pool map),
             then `bench/reference/bnn.py` (`fc_hd`, Algorithm 1)

Every conv runs at `Precision.HIGHEST`.  The stream is an integer sum
far below 2^24, so float32 holds it exactly.  `rnd` rounds the input,
every conv sum, every pooled sum and the stream after every add to a
lower precision for the control; the reference itself passes `None`.
Rows run in sub-blocks of SUB_BLOCK where the batch divides into them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import bnn
from bench.reference.conv import SUB_BLOCK, thermometer_pm1

HIGHEST = jax.lax.Precision.HIGHEST
DIMS = ("NHWC", "HWIO", "NHWC")


def layers(cfg: dict) -> list:
    """[(c_in, c_out, stride, shortcut)] of the stem then every conv, the
    shortcut "none", "identity" or "down"."""
    c_in = cfg["channels"] * cfg["encoding"]["width"]
    out = [(c_in, cfg["stem"], 1, "none")]
    c_in = cfg["stem"]
    per_stage = cfg["blocks_per_stage"] * cfg["convs_per_block"]
    for width, stride in zip(cfg["stages"], cfg["stage_strides"]):
        for j in range(per_stage):
            s = stride if j == 0 else 1
            down = s != 1 or c_in != width
            out.append((c_in, width, s, "down" if down else "identity"))
            c_in = width
    return out


def _conv(h, w, stride, rnd):
    return rnd(jax.lax.conv_general_dilated(
        h, w, (stride, stride), "SAME", dimension_numbers=DIMS,
        precision=HIGHEST))


def _sum_pool(x, p, rnd):
    win = (1, p, p, 1)
    return rnd(jax.lax.reduce_window(x, 0.0, jax.lax.add, win, win,
                                     "VALID"))


def resnet_hd(x, cfg: dict, convs, head, rnd=bnn._keep):
    """Head Hamming distances [B, C] of pixel rows `x` [B, n_in].

    convs : per entry of `layers(cfg)`, (w ±1 HWIO [k, k, ci, co], a [co],
            c [co], down), down None or the downsample's (w ±1 HWIO
            [1, 1, ci, co], a, c); a = s * m
    head  : [(W ±1 [C, n_feat], C as the bias cells hold it)]
    """
    side, ch = cfg["side"], cfg["channels"]
    width = cfg["encoding"]["width"]
    plan = layers(cfg)

    def block(xb):
        h = thermometer_pm1(rnd(xb).reshape(-1, side, side, ch), width)
        x = None
        for (w, a, c, down), (_, _, stride, shortcut) in zip(convs, plan):
            y = rnd(a * _conv(h, w, stride, rnd) + c)
            if shortcut == "identity":
                y = y + x
            elif shortcut == "down":
                wd, ad, cd = down
                hd = bnn.sign(_sum_pool(x, stride, rnd))
                y = y + rnd(ad * _conv(hd, wd, 1, rnd) + cd)
            x = rnd(y)
            h = bnn.sign(x)
        h = bnn.sign(_sum_pool(x, cfg["global_pool"], rnd))
        return bnn.fc_hd(h.reshape(h.shape[0], -1), head,
                         cfg["bias_cells"], rnd)

    n = x.shape[0]
    if n > SUB_BLOCK and n % SUB_BLOCK == 0:
        out = jax.lax.map(block, x.reshape(n // SUB_BLOCK, SUB_BLOCK, -1))
        return out.reshape(n, -1)
    return block(x)
