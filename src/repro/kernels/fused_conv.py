"""The deployed binary CNN as ±1 int8 products on the MXU.

The conv sibling of `kernels/fused_mlp.py`: one XLA program takes a
batch of raw [0,1] pixel rows to the vote head's Hamming distances, and
every binary dot in it runs on the matrix unit as an int8 product with
int32 sums:

    input:            float32 pixels [B, S*S*C] (HWC rows) -> the
                      deployment's `InputEncoding` -> ±1 int8 maps
                      [B, S, S, C0] (`encode_input`)
    per conv layer:   k x k conv of the ±1 map with the layer's ±1 int8
                      filters (int32 sums), zero padding for "same" — a
                      pad is 0, so it adds nothing to any dot, exactly as
                      a zero-padded ±1 conv — then + C_o and sign; a
                      pooled layer then takes s * maxpool(s * bits) per
                      channel: the OR of the window's sign bits for
                      s = +1 and the AND for s = -1 (`FoldedConvLayer.
                      pool_sign`).  An unpooled layer makes its sign
                      with no predicate (`sign_pm1`), so the conv's own
                      output fusion writes the int8 map and no separate
                      pass unpacks a bit-packed predicate; a pooled
                      layer keeps `where(v >= 0, ...)`, whose packed
                      predicate its pool reads at 1/8 of the bytes
    per residual
    conv layer:       the residual stream x (`stream_layer`):
                      x' = conv(sign(x)) + C + the shortcut (x, or the
                      downsample conv of sign(sumpool(x))), each conv's
                      int8 filters scaled by its BN scale a = s * m; a
                      pool sums x'; all of it in int32.  The next layer
                      reads sign(x') (`sign_pm1`, taken from the int32
                      value), and x' is stored at the layer's
                      `ConvMeta.stream_dtype`: int16 where the bound
                      `stream_bounds` proves from the deployed operands
                      fits, else int32; every read widens it to int32
    flatten:          NHWC, the order of the first FC layer's rows
    per FC layer:     ±1 int8 matmul, int32 sums, + C_j, sign
    head:             HD_j = (n_feat - h . w_j) / 2 + the bias cells'
                      constant distance (`fused_mlp.split_head`)

The thresholds' vote is the pipeline's, shared with every spec.  Every
product is ±1 (0 at a pad), or ±m for a residual layer, whose int8
filters carry the BN scale a = s * m (|m| <= 127), and every sum and
stream value is far below 2^31, so the int32 results are exact and
equal the unpacked float oracle (`kernels.ref.conv_votes_ref`) bit for
bit.  A stream stored as int16 holds every value exactly too: its
layer's bound (`stream_bounds`) is at most 32,767, so the narrowing
drops no bit, and it halves the bytes of the HBM-bound stream.

Why the MXU and not the packed VPU: at the CIFAR-10 ConvNet's shapes a
chip timing put a packed XNOR-popcount conv on the VPU 2.2-4.8x behind
the int8 MXU conv (PERF.md §6, PR 10), and the int8 path needs no
border mask, no channel repack and no SMEM weight budget.  The weights are the
program's arguments (`conv_operands`), 1 byte a weight in HBM, read
once per call; they are not jit constants (DESIGN.md §9, §10).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binarize
from repro.core.convnet import conv_out_side, conv_pads
from repro.kernels import fused_mlp

DIMS = ("NHWC", "HWIO", "NHWC")


@dataclasses.dataclass(frozen=True)
class ConvMeta:
    """Static shape info for one conv layer (square feature maps), and for
    a residual layer the type its stream is stored at, proven from the
    deployed operands by `conv_metas_for`."""

    c_in: int  # input channels
    stride: int
    pads: tuple[int, int]  # (low, high) zero padding per spatial axis
    pool: int  # max-pool (sum-pool for a residual layer) window and stride
    out_side: int  # side after the pool
    c_out: int  # output channels
    conv_side: int  # side before the pool
    residual: bool = False  # writes the residual stream
    shortcut: str = "none"  # convnet.SHORTCUTS
    # the stored stream's type (a residual layer; None for a plain one):
    # int16 where `stream_bounds` proves |x| <= 32,767, else int32
    stream_dtype: np.dtype | None = None


def _term(layer) -> int:
    """The most one residual conv adds to a stream value: the maximum
    over output channels of sum|w * m| + |C|, k^2 * c_in * m_o + |c_o|
    for ±1 filters (a pad adds 0)."""
    m = np.asarray(layer.residual, np.int64)
    return int(np.max(layer.n_bits * m + np.abs(np.asarray(layer.c,
                                                           np.int64))))


def stream_bounds(conv_layers: Sequence) -> tuple[int, ...]:
    """Per conv layer, the bound on |x| of the residual stream it stores
    into (0 for a plain layer), proven from the deployed operands.

    A stream starts at shortcut "none" or "down" (which reads only signs
    of the old stream) with the layer's term, plus the downsample conv's
    term for "down"; each "identity" layer adds its term, and a pool of
    p x p sums multiplies the bound by p^2.  A stream is one tensor from
    start to restart, stored at one type, so every layer of it takes the
    largest bound it reaches.  Each term is one maximum over its layer's
    output channels, not a per-channel chain, so the bound, and the
    program compiled from it, is the same for every draw of a
    configuration whose extremes of m and C are drawn.
    """
    bounds, b = [], 0
    for layer in conv_layers:
        if layer.residual is None:
            b = 0
        elif layer.shortcut == "identity":
            b = (b + _term(layer)) * layer.pool ** 2
        else:
            down = 0 if layer.down is None else _term(layer.down)
            b = (_term(layer) + down) * layer.pool ** 2
        bounds.append(b)
    for i in reversed(range(len(bounds) - 1)):
        if conv_layers[i + 1].shortcut == "identity":
            bounds[i] = max(bounds[i], bounds[i + 1])
    return tuple(bounds)


def conv_metas_for(conv_layers: Sequence, side: int) -> tuple[ConvMeta, ...]:
    """Static ConvMeta chain for a conv stack on `side` x `side` input.

    A shortcut must find a stream of its layer's output shape: "identity"
    the stream itself, "down" its stride x stride sum-pool.
    """
    metas = []
    s = side
    bounds = stream_bounds(conv_layers)
    for i, layer in enumerate(conv_layers):
        if metas and layer.c_in != metas[-1].c_out:
            raise ValueError(f"conv layer {i} takes {layer.c_in} channels, "
                             f"layer {i - 1} gives {metas[-1].c_out}")
        try:
            conv, out = conv_out_side(s, layer.k, layer.stride,
                                      layer.padding, layer.pool)
        except ValueError as e:
            raise ValueError(f"feature side {s}: {e} (layer {i})") from None
        if layer.shortcut != "none":
            if not (metas and metas[-1].residual):
                raise ValueError(f"conv layer {i}'s {layer.shortcut} "
                                 "shortcut has no residual stream to read")
            if layer.shortcut == "identity":
                fits = (s, layer.c_in) == (conv, layer.c_out)
            else:  # the down conv maps c_in to c_out
                fits = s // layer.stride == conv
            if not fits:
                raise ValueError(
                    f"conv layer {i}: a {layer.shortcut} shortcut of the "
                    f"{s}x{s}x{layer.c_in} stream does not give its "
                    f"{conv}x{conv}x{layer.c_out} output")
        residual = layer.residual is not None
        stream = np.int16 if bounds[i] <= np.iinfo(np.int16).max \
            else np.int32
        metas.append(ConvMeta(
            c_in=layer.c_in, stride=layer.stride,
            pads=conv_pads(s, layer.k, layer.stride, layer.padding),
            pool=layer.pool, out_side=out, c_out=layer.c_out,
            conv_side=conv, residual=residual, shortcut=layer.shortcut,
            stream_dtype=np.dtype(stream) if residual else None,
        ))
        s = out
    return tuple(metas)


def stream_bytes_per_row(metas: Sequence[ConvMeta]) -> int:
    """Bytes of residual stream the residual layers write a row at 4 B
    an element, the width its values are proven against, each at its
    conv output's shape (before any pool)."""
    return 4 * sum(m.conv_side ** 2 * m.c_out for m in metas if m.residual)


def stream_stored_bytes_per_row(metas: Sequence[ConvMeta]) -> int:
    """Bytes of residual stream the residual layers store a row, each at
    its own `stream_dtype` and its conv output's shape (before any
    pool)."""
    return sum(m.stream_dtype.itemsize * m.conv_side ** 2 * m.c_out
               for m in metas if m.residual)


def pm1_int8(w) -> np.ndarray:
    """±1 values -> int8 (the MXU operand form)."""
    return np.where(np.asarray(w) > 0, 1, -1).astype(np.int8)


def conv_operands(layer) -> tuple:
    """(HWIO ±1 int8 filters [k, k, c_in, c_out], C int32 [c_out],
    pool sign int8 [c_out]) of one plain `FoldedConvLayer`; of a residual
    one (HWIO int8 filters scaled by its magnitudes m, so a = s * m per
    channel, C int32 [c_out], the downsample's (filters, C) or ())."""
    w = np.transpose(pm1_int8(layer.weights_pm1), (1, 2, 3, 0))
    c = jnp.asarray(layer.c, jnp.int32)
    if layer.residual is None:
        s = np.where(layer.pool_or, 1, -1).astype(np.int8)
        return jnp.asarray(w), c, jnp.asarray(s)
    m = np.asarray(layer.residual, np.int64)
    if m.max() > 127:
        raise ValueError("residual magnitudes above 127 leave int8")
    down = () if layer.down is None else conv_operands(layer.down)[:2]
    return jnp.asarray((w * m).astype(np.int8)), c, down


def fc_operands(layer) -> tuple:
    """(±1 int8 [n_in, n_out], C int32 [n_out]) of one `FoldedLayer`."""
    return (jnp.asarray(pm1_int8(layer.weights_pm1).T),
            jnp.asarray(layer.c, jnp.int32))


def head_operands(head_rows, n_feat: int, bias_cells: int) -> tuple:
    """(±1 int8 [n_feat, C] class rows, int32 [C] bias-cell distance)."""
    rows, offset = fused_mlp.split_head(head_rows, n_feat, bias_cells)
    bits = np.asarray(binarize.unpack_bits(rows, n_feat))
    return (jnp.asarray(pm1_int8(bits.T)), jnp.asarray(offset, jnp.int32))


def weight_bytes(operands) -> int:
    """Bytes of the weight operands as the program holds them in HBM."""
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(operands))


def encode_input(x01, side: int, channels: int,
                 enc: binarize.InputEncoding):
    """[B, S*S*channels] [0,1] pixels (HWC rows) -> ±1 int8 [B, S, S, C0].

    `enc.encode_image_bits` of the images, so every encoding keeps the
    one definition the oracles use (C0 = channels * enc.width).
    """
    img = x01.reshape(x01.shape[0], side, side, channels)
    return 2 * enc.encode_image_bits(img).astype(jnp.int8) - 1


def sign_pm1(v):
    """int32 -> ±1 int8, +1 where v >= 0: `where(v >= 0, 1, -1)` with no
    predicate.

    The arithmetic shift gives 0 or -1 and OR 1 makes that +1 or -1, for
    every int32 value.  XLA stores a `v >= 0` predicate bit-packed along
    W and, where no pool reads the bits, expands them to the int8 map in
    a separate loop fusion: a full write and read of the map that does
    no arithmetic.  Without a predicate the producing conv's (or dot's)
    output fusion writes the int8 map itself.
    """
    return ((v >> 31) | 1).astype(jnp.int8)


def conv_layer(h, w, c, s, m: ConvMeta):
    """One conv layer on ±1 int8 maps: conv, + C, sign, pool -> ±1 int8."""
    y = jax.lax.conv_general_dilated(
        h, w, (m.stride, m.stride), (m.pads, m.pads),
        dimension_numbers=DIMS, preferred_element_type=jnp.int32,
    )
    if m.pool == 1:
        return sign_pm1(y + c)
    # the pool reads the packed predicate: 1/8 of an int8 map's bytes
    h = jnp.where(y + c >= 0, 1, -1).astype(jnp.int8)
    win = (1, m.pool, m.pool, 1)  # OR (s = +1) / AND (s = -1) of the bits
    return s * jax.lax.reduce_window(s * h, jnp.int8(-1), jax.lax.max,
                                     win, win, "VALID")


def _sum_pool(x, p: int):
    """p x p sums of a stream, in int32 whatever width x is stored at
    (four int16 values can pass 32,767)."""
    win = (1, p, p, 1)
    return jax.lax.reduce_window(x.astype(jnp.int32), jnp.int32(0),
                                 jax.lax.add, win, win, "VALID")


def stream_layer(h, x, w, c, down, m: ConvMeta):
    """One residual conv layer: ±1 int8 maps h and the stored stream x
    (None before the first) -> the stream after it in int32, which the
    caller signs and stores at `m.stream_dtype`."""
    y = jax.lax.conv_general_dilated(
        h, w, (m.stride, m.stride), (m.pads, m.pads),
        dimension_numbers=DIMS, preferred_element_type=jnp.int32,
    ) + c
    if m.shortcut == "identity":
        y = y + x.astype(jnp.int32)
    elif m.shortcut == "down":
        wd, cd = down
        xd = sign_pm1(_sum_pool(x, m.stride))
        y = y + jax.lax.conv_general_dilated(
            xd, wd, (1, 1), "VALID", dimension_numbers=DIMS,
            preferred_element_type=jnp.int32,
        ) + cd
    return y if m.pool == 1 else _sum_pool(y, m.pool)


def fc_layer(h, w, c):
    """One FC hidden layer on ±1 int8 rows -> ±1 int8."""
    return sign_pm1(jnp.dot(h, w, preferred_element_type=jnp.int32) + c)


def net_hd(x01, operands, metas: Sequence[ConvMeta], side: int,
           channels: int, enc: binarize.InputEncoding):
    """Head Hamming distances [B, C] int32 of a raw pixel batch.

    x01      : [B, side*side*channels] float32 pixels in [0,1] (HWC).
    operands : (conv, fc, head) — `conv_operands` per conv layer,
               `fc_operands` per FC hidden layer, `head_operands`.
    """
    conv, fc, (head_w, offset) = operands
    h = encode_input(x01, side, channels, enc)
    x = None  # the stored residual stream, once a residual layer starts it
    for ops, m in zip(conv, metas):
        if m.residual:
            y = stream_layer(h, x, *ops, m)
            h = sign_pm1(y)  # from the int32 value: `sign_pm1` is int32's
            x = y.astype(m.stream_dtype)
            if m.stream_dtype != np.int32:
                # XLA would sink the narrowing into the reading fusion and
                # store the int32 value; the barrier stores the int16 one
                x = jax.lax.optimization_barrier(x)
        else:
            h = conv_layer(h, *ops, m)
    h = h.reshape(h.shape[0], -1)
    for w, c in fc:
        h = fc_layer(h, w, c)
    dot = jnp.dot(h, head_w, preferred_element_type=jnp.int32)
    return (h.shape[1] - dot) // 2 + offset
