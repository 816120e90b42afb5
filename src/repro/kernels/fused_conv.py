"""Pallas kernel: the ENTIRE deployed binary CNN in one fused packed pass.

The conv sibling of `kernels/fused_mlp.py`, extending the paper's
"activations never leave the binary domain" property to convolutional
workloads (the dominant related-work axis — XNORBIN, ChewBaccaNN).  ONE
`pallas_call` per batch block executes

    per conv layer:   im2col folded into the packed layout — each of the
                      k*k taps of an output row is one strided sublane
                      load of the VMEM-resident channel-packed feature
                      map, XNOR-popcount accumulated against the filter's
                      tap word (no [B*OH*OW, k*k*C] patch matrix exists)
                      -> + C_o integer bias add -> sign
                      -> shift-or channel repack into uint32-pattern words
    flatten:          free — the FC stage reads the last feature tile in
                      place, with its first weight rows permuted to match
    per FC layer:     the fused_mlp hidden-layer step (packed matvec +
                      C + sign + repack)
    head:             fused multi-threshold CAM vote (33 compares
                      against one Hamming distance)

Only the channel-packed input feature map enters and only the int32
vote counts leave; every intermediate is VMEM/register resident.

Layout conventions (DESIGN.md §10):
  * the XLA twin and the host side see channel-packed NHWC maps
    [B, H, W, Cw] uint32, channel bits little-endian within each pixel's
    words, zero-padded to the word boundary per pixel;
  * inside the kernel the batch is the lane axis: word w of pixel (y, x)
    of batch lane b sits at [w * H + y, x, b] (int32, same bits), so a
    tap is a strided load along x and every XOR operand of a channel is
    one scalar weight word from SMEM;
  * filter rows are tap-major: [c_out, k*k*Cw] with word
    (dy*k + dx)*Cw + w holding tap (dy, dx)'s channel word w;
  * the flatten keeps the per-position word padding, so the first FC
    layer's rows must be packed with `pack_fc_rows_positionwise`
    (a plain `pack_bits` when c_out % 32 == 0 — the configs' choice).
  Pad bits are zero on BOTH operands of every Hamming distance, so they
  never contribute; logical dot widths stay k*k*c_in.

Correctness bar (tests/test_conv.py): bit-exact against the unpacked
±1 oracle `kernels.ref.conv_votes_ref` on multiple input sizes.

Silicon mode: identical contract to fused_mlp — an optional [P, B, C]
float32 `thr_samples` operand (from `physics.SearchPhysics.sample`)
replaces the shared thresholds in the head compare; the kernel itself
stays deterministic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import binarize
from repro.kernels import fused_mlp

WORD = 32


@dataclasses.dataclass(frozen=True)
class ConvMeta:
    """Static shape info for one fused conv layer (square feature maps)."""

    side: int  # input feature-map side
    cw_in: int  # packed channel words per input pixel
    k: int  # kernel side
    stride: int
    out_side: int  # VALID output side
    c_out: int  # output channels = bits produced per position
    cw_out: int  # packed channel words per output pixel
    n_bits: int  # logical dot width: k * k * c_in


def conv_metas_for(conv_layers: Sequence, side: int) -> tuple[ConvMeta, ...]:
    """Static ConvMeta chain for a conv stack on `side` x `side` input."""
    metas = []
    s = side
    for layer in conv_layers:
        if s < layer.k:
            raise ValueError(
                f"feature side {s} < kernel {layer.k} (layer {len(metas)})"
            )
        out = (s - layer.k) // layer.stride + 1
        metas.append(ConvMeta(
            side=s,
            cw_in=binarize.packed_width(layer.c_in),
            k=layer.k,
            stride=layer.stride,
            out_side=out,
            c_out=layer.c_out,
            cw_out=binarize.packed_width(layer.c_out),
            n_bits=layer.n_bits,
        ))
        s = out
    return tuple(metas)


def pack_conv_rows(layer) -> jax.Array:
    """FoldedConvLayer filters -> tap-major packed rows [c_out, k*k*Cw].

    Each filter's bits are packed per tap along the channel axis (same
    per-pixel word padding as the feature map), then taps concatenate
    in (dy, dx) scan order — the order both the kernel's tap loads and
    `conv_hd_packed`'s strided slices visit them.
    """
    bits = (np.asarray(layer.weights_pm1) > 0).astype(np.uint8)
    c_out, k = layer.c_out, layer.k
    words = binarize.np_pack_bits(bits.reshape(c_out * k * k, layer.c_in))
    return jnp.asarray(words.reshape(c_out, k * k * words.shape[-1]))


def pack_fc_rows_positionwise(w_bits: np.ndarray, n_pos: int,
                              c: int) -> jax.Array:
    """FC rows [n_out, n_pos*c] -> packed words matching the flatten.

    The conv flatten keeps each position's channel words padded to the
    word boundary, so the FIRST FC layer after the flatten must pack
    its weight rows with the same per-position alignment: bit (p, j)
    lands in word p*Cw + j//32.  Degenerates to a plain `pack_bits`
    when c % 32 == 0.  Pad bits are zero on both operands, so logical
    dot widths are unchanged.
    """
    n_out = w_bits.shape[0]
    if w_bits.shape[1] != n_pos * c:
        raise ValueError(
            f"rows have {w_bits.shape[1]} bits, expected {n_pos}*{c}"
        )
    words = binarize.np_pack_bits(
        np.asarray(w_bits, np.uint8).reshape(n_out * n_pos, c)
    )
    return jnp.asarray(words.reshape(n_out, n_pos * words.shape[-1]))


def bias_drive_words(bias_cells: int) -> np.ndarray:
    """Packed all-ones bias searchline words (logic '1' drive bits)."""
    return binarize.np_pack_bits(
        np.ones((1, bias_cells), np.uint8)
    )[0]


def conv_hd_packed(x, w, m: ConvMeta):
    """Per-position Hamming distances of one packed conv layer.

    x: [B, S, S, Cw] uint32; w: [c_out, k*k*Cw] tap-major rows.
    Returns [B, O, O, c_out] int32.  The im2col never materializes: tap
    (dy, dx) is a strided slice of the feature map, XNOR-popcount-
    accumulated against the filters' tap words.  Plain jnp — the XLA
    twin's conv step and the unpacked layer-by-layer benchmark baseline
    share it; the Pallas kernel's own tap loop is held to it bit for bit
    by tests/test_conv.py.
    """
    b = x.shape[0]
    hd = jnp.zeros((b, m.out_side, m.out_side, m.c_out), jnp.int32)
    span = (m.out_side - 1) * m.stride + 1
    for dy in range(m.k):
        for dx in range(m.k):
            xs = jax.lax.slice(
                x, (0, dy, dx, 0),
                (b, dy + span, dx + span, m.cw_in),
                (1, m.stride, m.stride, 1),
            )  # [B, O, O, Cw]
            tap = jax.lax.slice_in_dim(
                w, (dy * m.k + dx) * m.cw_in, (dy * m.k + dx + 1) * m.cw_in,
                axis=1,
            )  # [c_out, Cw]
            xor = jax.lax.bitwise_xor(
                xs[:, :, :, None, :], tap[None, None, None, :, :]
            )  # [B, O, O, c_out, Cw] — the bounded per-tap temporary
            hd = hd + jax.lax.population_count(xor).astype(jnp.int32).sum(-1)
    return hd


def _conv_layer_packed(x, w, c, m: ConvMeta):
    """One packed-domain conv layer: [B, S, S, Cw] -> [B, O, O, Cw_out].

    Plain jnp: the XLA twin's layer step and the oracle the kernel's
    `_conv_layer` is tested against.
    """
    b = x.shape[0]
    hd = conv_hd_packed(x, w, m)
    y = (m.n_bits - 2 * hd) + c[None, None, None, :]  # Eq. (3) pre-sign
    bits = (y >= 0).astype(jnp.uint32)  # sign, 0 -> +1
    pad = m.cw_out * WORD - m.c_out
    if pad:
        bits = jnp.concatenate(
            [bits,
             jnp.zeros((b, m.out_side, m.out_side, pad), jnp.uint32)],
            axis=-1,
        )
    shaped = bits.reshape(b, m.out_side, m.out_side, m.cw_out, WORD)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    return (shaped << shifts).sum(axis=-1, dtype=jnp.uint32)


def conv_stage_packed(x, conv_ws, conv_cs, metas, bias_words=None):
    """Run the conv stack + flatten in the packed domain (the XLA twin).

    x: [B, S, S, Cw0] uint32.  Returns the flattened packed query
    [B, n_pos * Cw_f (+ bias words)] feeding the FC stage; appends the
    all-ones bias drive words when `bias_words` is given (conv -> head
    direct, word-aligned flatten required).
    """
    for w, c, m in zip(conv_ws, conv_cs, metas):
        x = _conv_layer_packed(x, w, c, m)
    q = x.reshape(x.shape[0], -1)
    if bias_words is not None:
        bw = jnp.asarray(bias_words, jnp.uint32)
        q = jnp.concatenate(
            [q, jnp.broadcast_to(bw, (q.shape[0], bw.shape[0]))], axis=-1
        )
    return q


def feature_rows(m: ConvMeta) -> int:
    """Sublanes per spatial row of a conv output tile (side padded to 8)."""
    return -(-m.out_side // fused_mlp.SUB) * fused_mlp.SUB


def _conv_layer(in_ref, w_ref, c_ref, out_ref, m: ConvMeta):
    """One packed conv layer on batch-on-lanes feature tiles.

    in_ref : [cw_in * side, >= side, L] int32 — word w of pixel (y, x) of
             lane b at [w * side + y, x, b]
    out_ref: [cw_out * O, feature_rows(m), L], same layout; the padded
             columns x >= O are zeroed (the FC stage reads whole tiles)
    w_ref  : SMEM int32 `pack_conv_rows` words, [c_out, k*k*cw_in] flat
    Tap (dy, dx) of output row oy is one strided sublane load; each
    channel's XOR operand is a scalar weight word.
    """
    lanes = in_ref.shape[-1]
    o = m.out_side
    n_taps = m.k * m.k * m.cw_in
    for wo in range(m.cw_out):
        def row(oy, carry, wo=wo):
            taps = [
                in_ref[w * m.side + oy * m.stride + dy,
                       pl.ds(dx, o, stride=m.stride), :]
                for dy in range(m.k) for dx in range(m.k)
                for w in range(m.cw_in)
            ]  # tap order == pack_conv_rows word order

            def channel(i, acc):
                ch = wo * WORD + i
                hd = jnp.zeros((o, lanes), jnp.int32)
                for t, tap in enumerate(taps):
                    hd = hd + jax.lax.population_count(
                        tap ^ w_ref[ch * n_taps + t]
                    )
                y = m.n_bits - 2 * hd + c_ref[ch]  # Eq. (3) pre-sign
                return acc | jnp.left_shift((y >= 0).astype(jnp.int32), i)

            out_ref[wo * o + oy, pl.ds(0, o), :] = jax.lax.fori_loop(
                0, min(WORD, m.c_out - wo * WORD), channel,
                jnp.zeros((o, lanes), jnp.int32),
            )
            return carry

        jax.lax.fori_loop(0, o, row, 0)
    pad = out_ref.shape[1] - o
    if pad:
        out_ref[:, pl.ds(o, pad), :] = jnp.zeros(
            (out_ref.shape[0], pad, lanes), jnp.int32
        )


def flatten_rows(rows_packed: jax.Array, m: ConvMeta) -> jax.Array:
    """Position-wise packed FC rows -> SMEM-flat rows in the kernel's order.

    `pack_fc_rows_positionwise` orders words (position, channel word);
    the kernel's last conv tile holds word w of position (oy, ox) at
    [w * O + oy, ox].  Returns [N * cw * O * feature_rows(m)] int32 with
    zero words at the padded columns.
    """
    n, o, cw = rows_packed.shape[0], m.out_side, m.cw_out
    w = fused_mlp.to_int32(rows_packed).reshape(n, o, o, cw)
    w = jnp.pad(w.transpose(0, 3, 1, 2),
                ((0, 0), (0, 0), (0, 0), (0, feature_rows(m) - o)))
    return w.reshape(-1)


def _make_kernel(conv_metas, fc_metas, n_classes: int, noisy: bool):
    """Fused conv+FC+vote kernel body for a static layer stack.

    Ref order: x, (w, c) per conv layer, (w, c) per FC hidden layer,
    head_w, head_offset, thr, out, then scratch: one feature tile per
    conv layer, one word tile per FC hidden layer, the head distances.
    The FC/head tail is the fused_mlp step (same helpers).
    """
    n_conv, n_fc = len(conv_metas), len(fc_metas)

    def kernel(*refs):
        q_ref = refs[0]
        idx = 1
        for m in conv_metas:
            nxt = refs[5 + 2 * (n_conv + n_fc) + idx // 2]
            _conv_layer(q_ref, refs[idx], refs[idx + 1], nxt, m)
            q_ref, idx = nxt, idx + 2
        for m in fc_metas:
            nxt = refs[5 + 2 * (n_conv + n_fc) + idx // 2]
            fused_mlp.fc_layer(q_ref, refs[idx], refs[idx + 1], nxt, m)
            q_ref, idx = nxt, idx + 2
        head_w, head_off, thr_ref, out_ref = refs[idx: idx + 4]
        fused_mlp.head_votes(q_ref, head_w, head_off, thr_ref, refs[-1],
                             out_ref, n_classes, noisy)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("conv_metas", "layer_n_bits", "bias_cells", "bq",
                     "interpret", "head_direct"),
)
def fused_conv_votes(
    x_packed: jax.Array,
    conv_ws: tuple[jax.Array, ...],
    conv_cs: tuple[jax.Array, ...],
    conv_metas: tuple[ConvMeta, ...],
    layer_ws: tuple[jax.Array, ...],
    layer_cs: tuple[jax.Array, ...],
    layer_n_bits: tuple[int, ...],
    head_rows: jax.Array,
    thresholds: jax.Array,
    *,
    bias_cells: int,
    bq: int | None = None,
    interpret: bool = False,
    head_direct: bool = False,
    thr_samples: jax.Array | None = None,
) -> jax.Array:
    """Fused end-to-end binary-CNN vote counts (one kernel per block).

    x_packed    : [B, S, S, Cw0] uint32 — channel-packed encoded input
                  (`binarize.pack_bits` of the InputEncoding bits)
    conv_ws     : per conv layer [c_out, k*k*Cw] tap-major packed rows
                  (`pack_conv_rows`)
    conv_cs     : per conv layer [c_out] int32 folded BN constants
    conv_metas  : static `conv_metas_for` chain (shapes/strides)
    layer_ws    : FC-stage packed rows; the FIRST must be
                  `pack_fc_rows_positionwise` (flatten alignment)
    layer_cs / layer_n_bits / head_rows / thresholds / bias_cells / bq /
    interpret / thr_samples : exactly as in `fused_mlp.fused_mlp_votes`
    head_direct : True when there are no FC hidden layers — the flatten
                  (word-aligned: last conv c_out % 32 == 0) feeds the
                  head straight; its bias searchlines become a per-class
                  constant distance
    returns     : [B, C] int32 vote counts (== ref.conv_votes_ref)

    The input block is the whole image per batch lane: VMEM holds
    2 x Cw0*S*ceil8(S)*bq words (double-buffered) — DESIGN.md §10.
    """
    if len(conv_ws) != len(conv_cs) or len(conv_ws) != len(conv_metas):
        raise ValueError("conv operand/meta length mismatch")
    if len(layer_ws) != len(layer_cs) or len(layer_ws) != len(layer_n_bits):
        raise ValueError("fc operand length mismatch")
    if not conv_metas:
        raise ValueError("no conv layers — use fused_mlp.fused_mlp_votes")
    m0, mf = conv_metas[0], conv_metas[-1]
    if x_packed.shape[1:] != (m0.side, m0.side, m0.cw_in):
        raise ValueError(
            f"x_packed shape {x_packed.shape} does not match the first "
            f"conv layer's [B, {m0.side}, {m0.side}, {m0.cw_in}]"
        )
    if head_direct:
        if layer_ws:
            raise ValueError("head_direct=True with FC hidden layers")
        if mf.c_out % WORD:
            raise ValueError(
                "conv -> head-direct needs a word-aligned flatten: last "
                f"conv c_out {mf.c_out} % 32 != 0"
            )
    elif not layer_ws:
        raise ValueError("no FC layers and head_direct=False")

    bq = fused_mlp.block_rows(bq, interpret)
    b, side = x_packed.shape[0], m0.side
    bp = fused_mlp.pad_batch(b, bq)
    x = jnp.pad(fused_mlp.to_int32(x_packed),
                ((0, bp - b), (0, 0), (0, 0), (0, 0)))
    x = x.transpose(3, 1, 2, 0).reshape(m0.cw_in * side, side, bp)
    operands = [x]
    specs = [pl.BlockSpec((x.shape[0], side, bq), lambda i: (0, 0, i))]
    scratch = []
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    for w, c, m in zip(conv_ws, conv_cs, conv_metas):
        operands += [fused_mlp.to_int32(w).reshape(-1),
                     jnp.asarray(c, jnp.int32)]
        specs += [smem, smem]
        scratch.append(pltpu.VMEM(
            (m.cw_out * m.out_side, feature_rows(m), bq), jnp.int32
        ))

    n_feat = mf.out_side * mf.out_side * mf.c_out
    fc_metas = []
    for i, (w, c, n_bits) in enumerate(zip(layer_ws, layer_cs,
                                           layer_n_bits)):
        m = fused_mlp._LayerMeta(n_bits=int(n_bits), n_out=int(w.shape[0]))
        fc_metas.append(m)
        operands += [flatten_rows(w, mf) if i == 0
                     else fused_mlp.unit_words(w),
                     jnp.asarray(c, jnp.int32)]
        specs += [smem, smem]
        scratch.append(pltpu.VMEM(
            (fused_mlp.word_rows(binarize.packed_width(m.n_out)),
             fused_mlp.SUB, bq),
            jnp.int32,
        ))
        n_feat = m.n_out
    rows, offset = fused_mlp.split_head(head_rows, n_feat, bias_cells)
    head_w = (flatten_rows(rows, mf) if head_direct
              else fused_mlp.unit_words(rows))
    head_ops, head_specs, n_groups = fused_mlp.head_operands(
        head_w, offset, thresholds, thr_samples, bp, bq
    )
    scratch.append(pltpu.VMEM((n_groups, fused_mlp.SUB, bq), jnp.int32))

    out = pl.pallas_call(
        _make_kernel(tuple(conv_metas), tuple(fc_metas),
                     head_rows.shape[0], thr_samples is not None),
        grid=(bp // bq,),
        in_specs=specs + head_specs,
        out_specs=pl.BlockSpec((n_groups, fused_mlp.SUB, bq),
                               lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n_groups, fused_mlp.SUB, bp),
                                       jnp.int32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="fused_conv",
    )(*operands, *head_ops)
    return fused_mlp.votes_from_tiles(out, b, head_rows.shape[0])
