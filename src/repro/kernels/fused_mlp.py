"""Pallas TPU kernel: the ENTIRE deployed BNN in one fused packed-domain pass.

The paper's headline property is that weights AND activations never leave
the binary domain: hidden activations are regenerated inside the CAM
array, layer after layer, with no full-precision round trip (that is what
buys 560 K inf/s at 0.8 mW).  The layer-by-layer TPU translation loses
this: each `sign(Wx + C)` used to return unpacked ±1 floats to HBM, get
re-packed by a host-level `pack_bits`, and only then feed the next layer
(three HBM round trips per layer).

This kernel is the TPU translation of "activations stay in the array"
(DESIGN.md §4): ONE `pallas_call` per batch block executes

    per hidden layer:  XNOR-popcount matvec over packed int32 words
                       -> + C_j integer bias add -> sign
                       -> shift-or repack into the next layer's words
    final layer:       fused 33-threshold CAM vote (cam_search semantics)

with every intermediate — Hamming distances, repacked activation words —
resident in VMEM/vector registers.  Only the packed input batch enters
and only the int32 vote counts leave.

Layout (what Mosaic lowers; DESIGN.md §4):
  * the batch runs along the 128 lanes: a block holds `bq` rows (128 by
    default — one vreg of lanes);
  * packed words sit on sublanes, 8 to a tile: a layer input is a
    `[R, 8, bq]` int32 "word tile" block, word r*8 + s of row b at
    [r, s, b];
  * weights live in SMEM as unit-major int32 scalars
    `[N, R, 8]` (zero-padded words), so the XOR operand of each tile is
    built from 8 scalar reads — no lane slicing, no lane-splitting
    reshape, no unsigned reduction, no narrow-minor temporary;
  * a unit's Hamming distance is the sublane sum of the accumulated
    `[8, bq]` popcounts; 32 units' sign bits shift-or into one word row
    of the next layer's tile.
The head's bias searchlines (always logic '1') are folded into a
per-class constant distance, so every layer input is just the previous
layer's sign bits.

Weights for the paper-scale models are tiny in packed form (784x128 bits
= 12.8 KiB); all layers sit in SMEM (1 MiB on v5e) for the whole call.

Correctness bar (tests/test_pipeline.py): bit-exact against the
`bnn.folded_forward_exact` + `ensemble.votes_fused` digital oracle, and
against the XLA twin (`pipeline._head_hd_xla`) on every spec.

Silicon mode (DESIGN.md §8): the head vote optionally consumes a
precomputed [P, B, C] float32 block of noise-sampled per-pass thresholds
(`thr_samples`, produced by `core/physics.SearchPhysics.sample` outside
the kernel) — the HD-once/compare-P-times amortization is unchanged and
the kernel stays deterministic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import binarize

WORD = 32
SUB = 8  # sublanes per vreg: packed words are tiled 8 to a row
LANES = 128  # lanes per vreg: the default batch rows per kernel block


@dataclasses.dataclass(frozen=True)
class _LayerMeta:
    """Static shape info for one fused hidden layer."""

    n_bits: int  # logical input bits (the XNOR-popcount dot width)
    n_out: int  # neurons = activation bits produced


def word_rows(kw: int) -> int:
    """Rows of the [R, 8, bq] word tile that hold `kw` packed words."""
    return -(-kw // SUB)


def block_rows(bq: int | None, interpret: bool) -> int:
    """Batch rows per kernel block: one vreg of lanes unless overridden.

    The batch is the lane axis, so a compiled block must be a multiple
    of 128 rows; interpret mode (CPU semantics tests) takes any size.
    """
    bq = LANES if bq is None else int(bq)
    if bq <= 0 or (not interpret and bq % LANES):
        raise ValueError(f"bq={bq} must be a positive multiple of {LANES}")
    return bq


def pad_batch(n: int, bq: int) -> int:
    """Batch size rounded up to a whole number of kernel blocks."""
    return -(-n // bq) * bq


def to_int32(words: jax.Array) -> jax.Array:
    """uint32 packed words -> int32 (same bits; Mosaic has no unsigned
    reductions or casts, and XOR/popcount are sign-agnostic)."""
    return jax.lax.bitcast_convert_type(jnp.asarray(words), jnp.int32)


def word_tiles(x_packed: jax.Array, bp: int) -> jax.Array:
    """[B, Kw] packed rows -> [R, 8, bp] int32 word tiles (batch on lanes)."""
    b, kw = x_packed.shape
    r = word_rows(kw)
    x = jnp.pad(to_int32(x_packed), ((0, bp - b), (0, r * SUB - kw)))
    return x.T.reshape(r, SUB, bp)


def unit_words(rows_packed: jax.Array) -> jax.Array:
    """[N, Kw] packed weight rows -> flat [N * R * 8] int32 for SMEM."""
    n, kw = rows_packed.shape
    w = jnp.pad(to_int32(rows_packed), ((0, 0), (0, word_rows(kw) * SUB - kw)))
    return w.reshape(-1)


def split_head(head_rows: jax.Array, n_feat: int, bias_cells: int):
    """Head rows -> (feature-part rows [C, packed_width(n_feat)], [C] offsets).

    A head query is `n_feat` activation bits followed by `bias_cells`
    searchlines driven to logic '1'; its Hamming distance splits into the
    distance over the activation bits plus a per-class constant — the
    number of bias cells holding a 0.
    """
    bits = binarize.unpack_bits(head_rows, n_feat + bias_cells)
    offset = bias_cells - bits[:, n_feat:].astype(jnp.int32).sum(-1)
    return binarize.pack_bits(bits[:, :n_feat]), offset


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def unit_hd(q_ref, w_ref, unit):
    """Hamming distance of one unit's packed row against every lane's input.

    q_ref : [R, S, L] int32 word tiles (S a multiple of 8 sublanes)
    w_ref : SMEM int32 [N * R * S] unit-major rows (zero-padded words)
    Returns [1, L] int32.  Zero pad words on both operands add nothing.
    """
    n_rows, s, lanes = q_ref.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (s, lanes), 0)

    def body(r, acc):
        base = (unit * n_rows + r) * s
        wv = jnp.zeros((s, lanes), jnp.int32)
        for i in range(s):
            wv = jnp.where(sub == i, w_ref[base + i], wv)
        return acc + jax.lax.population_count(q_ref[r] ^ wv)

    acc = jax.lax.fori_loop(
        0, n_rows, body, jnp.zeros((s, lanes), jnp.int32)
    )
    return jnp.sum(acc, axis=0, keepdims=True)


def fc_layer(q_ref, w_ref, c_ref, out_ref, m: _LayerMeta):
    """One hidden layer: sign(n_bits - 2*HD + C) packed into out_ref's words.

    out_ref: [R', 8, L] word tiles of the next layer; pad words are
    written as zeros (they meet zero weight words in the next matvec).
    """
    lanes = q_ref.shape[-1]
    n_words = -(-m.n_out // WORD)
    for j in range(out_ref.shape[0] * SUB):
        if j < n_words:
            def unit(i, acc, j=j):
                n = j * WORD + i
                y = m.n_bits - 2 * unit_hd(q_ref, w_ref, n) + c_ref[n]
                return acc | jnp.left_shift((y >= 0).astype(jnp.int32), i)

            word = jax.lax.fori_loop(
                0, min(WORD, m.n_out - j * WORD), unit,
                jnp.zeros((1, lanes), jnp.int32),
            )
        else:
            word = jnp.zeros((1, lanes), jnp.int32)
        out_ref[j // SUB, pl.ds(j % SUB, 1), :] = word


def head_votes(q_ref, w_ref, off_ref, thr_ref, hd_ref, out_ref,
               n_classes: int, noisy: bool):
    """Fused multi-threshold CAM vote: HD once per class, P compares.

    hd_ref / out_ref: [Cg, 8, L] (class c at [c // 8, c % 8]).  thr_ref
    is the SMEM [P] schedule, or with `noisy` the VMEM [P, Cg, 8, L]
    float32 sample block.
    """
    for c in range(n_classes):
        hd_ref[c // SUB, pl.ds(c % SUB, 1), :] = (
            unit_hd(q_ref, w_ref, c) + off_ref[c]
        )
    n_pass = thr_ref.shape[0]
    for g in range(out_ref.shape[0]):
        hd = hd_ref[g]
        if noisy or jnp.issubdtype(thr_ref.dtype, jnp.floating):
            hd = hd.astype(jnp.float32)
        votes = jnp.zeros(hd.shape, jnp.int32)
        for p in range(n_pass):
            t = thr_ref[p, g] if noisy else thr_ref[p]
            votes = votes + (hd <= t).astype(jnp.int32)
        out_ref[g] = votes


def head_operands(head_w, offset, thresholds, thr_samples, bp: int,
                  bq: int):
    """Operands, specs and the class-group count of the vote head.

    head_w : SMEM-flat class rows in the layout of the head's input tile
    offset : [C] constant distance of the bias searchlines
    """
    n_classes = offset.shape[0]
    n_groups = -(-n_classes // SUB)
    if jnp.issubdtype(thresholds.dtype, jnp.floating):
        thr = thresholds.astype(jnp.float32)
    else:
        thr = thresholds.astype(jnp.int32)
    operands = [head_w, offset.astype(jnp.int32)]
    specs = [_smem(), _smem()]
    if thr_samples is None:
        operands.append(thr)
        specs.append(_smem())
    else:
        p = thr.shape[0]
        if thr_samples.shape[::2] != (p, n_classes):
            raise ValueError(
                f"thr_samples shape {thr_samples.shape} != [{p}, B, "
                f"{n_classes}]"
            )
        ts = jnp.pad(
            thr_samples.astype(jnp.float32),
            ((0, 0), (0, bp - thr_samples.shape[1]),
             (0, n_groups * SUB - n_classes)),
        )
        operands.append(
            ts.reshape(p, bp, n_groups, SUB).transpose(0, 2, 3, 1)
        )
        specs.append(
            pl.BlockSpec((p, n_groups, SUB, bq), lambda i: (0, 0, 0, i))
        )
    return operands, specs, n_groups


def votes_from_tiles(out: jax.Array, b: int, n_classes: int) -> jax.Array:
    """[Cg, 8, bp] kernel votes -> [B, C] int32."""
    return out.reshape(-1, out.shape[-1])[:n_classes, :b].T


def _make_kernel(metas: Sequence[_LayerMeta], n_classes: int, noisy: bool):
    """Build the fused kernel body for a static layer stack.

    Ref order: x, (w, c) per hidden layer, head_w, head_offset, thr, out,
    then scratch: one word tile per hidden layer output, and the head
    distance tile.
    """
    n_hidden = len(metas)

    def kernel(*refs):
        x_ref = refs[0]
        head_w, head_off, thr_ref, out_ref = refs[
            1 + 2 * n_hidden: 5 + 2 * n_hidden
        ]
        acts = refs[5 + 2 * n_hidden: 5 + 3 * n_hidden]
        hd_ref = refs[-1]
        q_ref = x_ref
        for i, m in enumerate(metas):
            fc_layer(q_ref, refs[1 + 2 * i], refs[2 + 2 * i], acts[i], m)
            q_ref = acts[i]
        head_votes(q_ref, head_w, head_off, thr_ref, hd_ref, out_ref,
                   n_classes, noisy)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("layer_n_bits", "bias_cells", "bq", "interpret"),
)
def fused_mlp_votes(
    x_packed: jax.Array,
    layer_ws: tuple[jax.Array, ...],
    layer_cs: tuple[jax.Array, ...],
    layer_n_bits: tuple[int, ...],
    head_rows: jax.Array,
    thresholds: jax.Array,
    *,
    bias_cells: int,
    bq: int | None = None,
    interpret: bool = False,
    thr_samples: jax.Array | None = None,
) -> jax.Array:
    """Fused end-to-end deployed-BNN vote counts.

    x_packed    : [B, Kw0] uint32 — packed ±1 input activations
    layer_ws    : per hidden layer [N_l, Kw_l] uint32 packed weight rows
    layer_cs    : per hidden layer [N_l] int32 folded BN constants
    layer_n_bits: per hidden layer logical input bit count
    head_rows   : [C, Kw_h] uint32 packed class rows (bias cells included)
    thresholds  : [P] HD tolerances (Algorithm 1 sweep; int32 for the
                  ideal sweep, float32 for calibrated knob-achieved values)
    bias_cells  : bias searchlines appended to the head query
    bq          : batch rows per kernel block (default 128, one vreg of
                  lanes; compiled blocks need a multiple of 128)
    interpret   : run the kernel body through the Pallas interpreter
                  (CPU semantics tests only: the pipeline runs the
                  kernel on the TPU alone)
    thr_samples : optional [P, B, C] float32 noise-sampled per-pass
                  thresholds (from `physics.SearchPhysics.sample`);
                  replaces `thresholds` in the head compare — the
                  silicon-noise fused path.  Sampling happens OUTSIDE the
                  kernel; the kernel only consumes.
    returns     : [B, C] int32 vote counts (== ensemble.votes_fused, or
                  ensemble.votes_fused_noisy when thr_samples is given)

    With no hidden layers, `x_packed` must already be the head query
    (activation bits + bias drive bits), as built by `cam.query_with_bias`.
    """
    if len(layer_ws) != len(layer_cs) or len(layer_ws) != len(layer_n_bits):
        raise ValueError("layer_ws / layer_cs / layer_n_bits length mismatch")
    # shape discipline: the input must line up with its first operand —
    # a mismatch (e.g. a head-only query packed WITHOUT the bias drive
    # bits) would otherwise silently truncate the HD loop and return
    # wrong votes
    first_kw = (layer_ws[0] if layer_ws else head_rows).shape[1]
    if x_packed.shape[1] != first_kw:
        raise ValueError(
            f"x_packed width {x_packed.shape[1]} does not match the first "
            f"operand's packed width {first_kw}; for a head-only net the "
            "query must include the bias drive bits (cam.query_with_bias)"
        )
    bq = block_rows(bq, interpret)
    b = x_packed.shape[0]
    bp = pad_batch(b, bq)
    x = word_tiles(x_packed, bp)

    metas, operands, specs, scratch = [], [x], [], []
    specs.append(pl.BlockSpec((x.shape[0], SUB, bq), lambda i: (0, 0, i)))
    for w, c, n_bits in zip(layer_ws, layer_cs, layer_n_bits):
        m = _LayerMeta(n_bits=int(n_bits), n_out=int(w.shape[0]))
        metas.append(m)
        operands += [unit_words(w), jnp.asarray(c, jnp.int32)]
        specs += [_smem(), _smem()]
        scratch.append(pltpu.VMEM(
            (word_rows(binarize.packed_width(m.n_out)), SUB, bq), jnp.int32
        ))
    for prev, nxt in zip(layer_ws[:-1], layer_ws[1:]):
        if binarize.packed_width(prev.shape[0]) != nxt.shape[1]:
            raise ValueError("hidden layer widths do not chain")
    if metas:
        rows, offset = split_head(head_rows, metas[-1].n_out, bias_cells)
    else:  # the input already is the full head query
        rows = head_rows
        offset = jnp.zeros((head_rows.shape[0],), jnp.int32)
    head_ops, head_specs, n_groups = head_operands(
        unit_words(rows), offset, thresholds, thr_samples, bp, bq
    )
    scratch.append(pltpu.VMEM((n_groups, SUB, bq), jnp.int32))

    out = pl.pallas_call(
        _make_kernel(tuple(metas), head_rows.shape[0],
                     thr_samples is not None),
        grid=(bp // bq,),
        in_specs=specs + head_specs,
        out_specs=pl.BlockSpec((n_groups, SUB, bq), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n_groups, SUB, bp), jnp.int32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="fused_mlp",
    )(*operands, *head_ops)
    return votes_from_tiles(out, b, head_rows.shape[0])
