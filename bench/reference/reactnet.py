"""Plain reference of ReActNet-A, in `jax.numpy` float32.

Written from the configuration file and ReActNet's equations alone
(arXiv:2003.03488 Sec. 3.1, Fig. 2 and Sec. 3.2, Eq. 2-4, on the
MobileNetV1 layout of arXiv:1704.04861 Table 1); it imports nothing of
the program under test.  A request row is a [side, side, channels] image
of [0, 1] pixels in HWC order.  Every BN is integer, a = s * m per
channel plus C, sign(v) is +1 for v >= 0, and every shift is an
integer.  Then:

    input    the thermometer code of `bench/reference/conv.py`, ±1
    stem     x_0 = a_0 * conv3x3/2(input) + C_0, zero padded ("SAME", as
             XLA pads it), no sign: the start of the residual stream
    blocks   each of the file's blocks (c_out, stride) on a stream of c
             channels runs two sub-blocks, a 3x3 conv c -> c of the
             block's stride and a 1x1 conv c -> c_out, each
               h  = sign(x - alpha)                     RSign
               y  = a * conv(h) + C + sc(x)
               x' = y - gamma + zeta                    y >= gamma
                    floor(beta * (y - gamma)) + zeta    otherwise  RPReLU
             with sc(x) = x, or for the 3x3 of a stride-2 block the
             AvgPool 2x2 of x floored to an integer, floor(sum / 4); a
             1x1 with c_out = 2c is two c -> c convs, each with its own
             BN and the shortcut x, concatenated on channels
    head     h = sign(sum of x_L over its global_pool x global_pool map),
             then `bench/reference/bnn.py` (`fc_hd`, Algorithm 1)

Every conv runs at `Precision.HIGHEST`.  The stream is an integer sum
below 2^24 (the configuration's bound is 9,918,776 after the global
pool), and beta = q / 8, so float32 holds every value, quarter and
eighth exactly and `jnp.floor` is exact.  `rnd` rounds the input, every
conv sum, every pooled sum and the stream after every sub-block to a
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
    """[(c_in, c_out, k, stride, shortcut)] of the stem, then each block's
    3x3 and 1x1 sub-blocks; the shortcut "none", "identity", "avg" (the
    3x3 of a stride-2 block) or "dup" (a 1x1 that doubles the width)."""
    c = cfg["stem"]
    out = [(cfg["channels"] * cfg["encoding"]["width"], c, 3,
            cfg["stem_stride"], "none")]
    for c_out, stride in cfg["blocks"]:
        out.append((c, c, 3, stride, "avg" if stride == 2 else "identity"))
        if c_out not in (c, 2 * c):
            raise ValueError(f"a block takes {c} channels to {c} or {2 * c}")
        out.append((c, c_out, 1, 1, "dup" if c_out == 2 * c else "identity"))
        c = c_out
    return out


def _conv(h, w, stride, rnd):
    return rnd(jax.lax.conv_general_dilated(
        h, w, (stride, stride), "SAME", dimension_numbers=DIMS,
        precision=HIGHEST))


def _sum_pool(x, p, rnd):
    win = (1, p, p, 1)
    return rnd(jax.lax.reduce_window(x, 0.0, jax.lax.add, win, win,
                                     "VALID"))


def reactnet_hd(x, cfg: dict, convs, head, rnd=bnn._keep):
    """Head Hamming distances [B, C] of pixel rows `x` [B, n_in].

    convs : per entry of `layers(cfg)`, (parts, alpha, prelu): parts
            [(w ±1 HWIO [k, k, ci, co], a [co], c [co])], one conv, or
            the two halves of a "dup" 1x1; alpha [ci] or None (the stem);
            prelu (gamma, beta, zeta) [c_out] each or None (the stem)
    head  : [(W ±1 [C, n_feat], C as the bias cells hold it)]
    """
    side, ch = cfg["side"], cfg["channels"]
    width = cfg["encoding"]["width"]
    plan = layers(cfg)

    def block(xb):
        h = thermometer_pm1(rnd(xb).reshape(-1, side, side, ch), width)
        x = None
        for (parts, alpha, prelu), (*_, stride, shortcut) in zip(convs,
                                                                  plan):
            if alpha is not None:
                h = bnn.sign(x - alpha)
            ys = [rnd(a * _conv(h, w, stride, rnd) + c) for w, a, c in parts]
            if shortcut == "identity":
                ys = [ys[0] + x]
            elif shortcut == "avg":
                ys = [ys[0] + jnp.floor(_sum_pool(x, 2, rnd) / 4)]
            elif shortcut == "dup":
                ys = [y + x for y in ys]
            y = jnp.concatenate(ys, axis=-1)
            if prelu is not None:
                gamma, beta, zeta = prelu
                d = y - gamma
                y = jnp.where(d >= 0, d, jnp.floor(beta * d)) + zeta
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
