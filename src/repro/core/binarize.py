"""Binarization primitives: sign with straight-through estimator, bit
packing/unpacking, and Hamming-distance utilities.

Conventions (match the paper, Sec. II-B):
  logical bit b in {0, 1}  <->  value v = 2b - 1 in {-1, +1}
  weight/activation "match" (XNOR == 1)  <->  product v_w * v_x = +1

Packed representation: bits are packed little-endian into uint32 words along
the last axis; `valid_len` tracks the logical (unpadded) bit length.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32


@jax.custom_vjp
def sign_ste(x):
    """sign(x) in {-1, +1} with the clipped straight-through estimator.

    Forward: sign(x) (0 maps to +1, matching the paper's logic-'1' coding).
    Backward: dL/dx = dL/dy * 1[|x| <= 1]  (Hinton STE / BinaryConnect).
    """
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _sign_ste_fwd(x):
    return sign_ste(x), x


def _sign_ste_bwd(x, g):
    return (g * (jnp.abs(x) <= 1.0).astype(g.dtype),)


sign_ste.defvjp(_sign_ste_fwd, _sign_ste_bwd)


def to_bits(values):
    """±1 values (any float/int dtype) -> {0,1} uint8 bits."""
    return (values > 0).astype(jnp.uint8)


def from_bits(bits, dtype=jnp.float32):
    """{0,1} bits -> ±1 values."""
    return (2 * bits.astype(jnp.int8) - 1).astype(dtype)


def packed_width(n_bits: int) -> int:
    """uint32 words needed for n_bits packed bits (ceil division)."""
    return -(-n_bits // WORD)


# Power-of-two vectors for the dot-product pack fast path. The word is
# packed as two 16-bit halves so every partial sum stays int32-exact
# (a single 32-bit dot would need bit 31 = 2^31, which overflows int32).
_POW2_HALF = np.asarray(1 << np.arange(WORD // 2), np.int32)


def pack_bits(bits):
    """Pack {0,1} bits along the last axis into uint32 words (little-endian).

    Pads with 0 to a multiple of 32. Padding bits are 0 on both operands of a
    Hamming distance, so XOR over padding contributes nothing.

    Fast path: each 16-bit half-word is a single dot against the
    power-of-two vector (int32-exact), and the two halves combine with one
    shift-or — replacing the shift-broadcast-sum that materialized a
    [..., kw, 32] uint32 temporary and reduced it lane by lane.
    """
    *lead, k = bits.shape
    kw = packed_width(k)
    pad = kw * WORD - k
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * len(lead) + [(0, pad)])
    halves = bits.reshape(*lead, kw * 2, WORD // 2).astype(jnp.int32)
    pow2 = jnp.asarray(_POW2_HALF)
    words16 = jax.lax.dot_general(
        halves, pow2,
        (((halves.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.uint32)
    words16 = words16.reshape(*lead, kw, 2)
    return words16[..., 0] | (words16[..., 1] << jnp.uint32(16))


def pack_bits_reference(bits):
    """The original shift-broadcast-sum pack (kept as oracle/baseline)."""
    *lead, k = bits.shape
    kw = packed_width(k)
    pad = kw * WORD - k
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * len(lead) + [(0, pad)])
    bits = bits.reshape(*lead, kw, WORD).astype(jnp.uint32)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    return (bits << shifts).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(words, n_bits: int):
    """uint32 words -> {0,1} uint8 bits, truncated to n_bits."""
    *lead, kw = words.shape
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*lead, kw * WORD)[..., :n_bits].astype(jnp.uint8)


def pack_pm1(values):
    """±1 values -> packed uint32 words."""
    return pack_bits(to_bits(values))


# Host packing splits a batch into at most _HOST_PACK_THREADS contiguous
# chunks, one compare and one `packbits` each, the first on the calling
# thread.  NumPy releases the GIL inside both loops; finer blocks lose
# more to handing the GIL between threads than they gain in cache, and
# four streams already take the memory bandwidth of a TPU v5e host
# (timings in PERF.md).
_HOST_PACK_THREADS = min(4, os.cpu_count() or 1)
_HOST_CHUNK_BYTES = 1 << 20  # the least input a chunk is given


@functools.cache
def _host_pack_pool() -> ThreadPoolExecutor:
    return ThreadPoolExecutor(max_workers=_HOST_PACK_THREADS - 1,
                              thread_name_prefix="pack_pm1_host")


def pack_pm1_host(values) -> np.ndarray:
    """`pack_pm1` of a host [B, n] array, on the host: uint32 [B, kw].

    Bit-equal to `pack_pm1` on the device: `values` is first cast to the
    dtype JAX would stage it as (`canonicalize_dtype`: float64 ->
    float32, int64 -> int32), so a float64 1e-50 packs as 0 there as here.
    0, -0 and NaN give bit 0; rows pad to whole words with 0 bits.
    """
    x = np.asarray(values)
    dtype = jax.dtypes.canonicalize_dtype(x.dtype)
    b, n = x.shape
    nbytes = -(-n // 8)
    out = np.empty((b, packed_width(n)), "<u4")
    view = out.view(np.uint8)

    def pack_rows(lo: int, hi: int) -> None:
        view[lo:hi, :nbytes] = np.packbits(
            x[lo:hi].astype(dtype, copy=False) > 0, axis=-1,
            bitorder="little")
        view[lo:hi, nbytes:] = 0

    least = max(1, _HOST_CHUNK_BYTES // max(1, n * dtype.itemsize))
    chunk = max(least, -(-b // _HOST_PACK_THREADS))
    futures = [_host_pack_pool().submit(pack_rows, lo, min(lo + chunk, b))
               for lo in range(chunk, b, chunk)]
    try:
        pack_rows(0, min(chunk, b))
    finally:
        for f in futures:
            f.result()
    return out


def hamming_packed(a, b):
    """Hamming distance between packed bit vectors (broadcasts leading dims)."""
    return jnp.bitwise_count(jnp.bitwise_xor(a, b)).astype(jnp.int32).sum(-1)


def hamming_pm1(a, b):
    """Hamming distance between ±1 vectors: #positions where they differ."""
    return jnp.sum(a * b < 0, axis=-1).astype(jnp.int32)


def dot_from_hd(hd, n_bits):
    """XNOR-popcount 'dot product' from Hamming distance.

    matches - mismatches = (n - hd) - hd = n - 2*hd  ==  <v_a, v_b> in ±1.
    """
    return n_bits - 2 * hd


def hd_from_dot(dot, n_bits):
    """Inverse of `dot_from_hd`: Hamming distance from the ±1 dot."""
    return (n_bits - dot) // 2


@functools.partial(jax.jit, static_argnames=("n_bits",))
def binary_matvec_packed(w_packed, x_packed, n_bits: int):
    """y_j = sum_i XNOR(+/-)(W_ji, x_i) over packed rows.

    w_packed: [N, Kw] uint32;  x_packed: [..., Kw] uint32.
    Returns [..., N] int32 dot products in the ±1 domain.

    Routed through the tiled Pallas popcount GEMM (kernels.binary_gemm) —
    the broadcast XOR it replaces materialized an O(B*N*Kw) uint32
    temporary in HBM; the kernel keeps each (bm, bn) tile's working set
    in VMEM.
    """
    from repro.kernels import ops  # deferred: core stays import-light

    *lead, kw = x_packed.shape
    hd = ops.binary_gemm_hd(
        x_packed.reshape(-1, kw), w_packed, bm=128, bn=128
    )
    return dot_from_hd(hd, n_bits).reshape(*lead, w_packed.shape[0])


# ---------------------------------------------------------------------------
# Binary input layer: [0, 1] intensity -> multi-bit binary codes
# ---------------------------------------------------------------------------
# The paper's end-to-end claim binarizes the INPUT layer too (typical BNNs
# keep it full precision).  A single sign threshold throws away all
# magnitude information; these encodings expand each [0, 1] intensity into
# `width` binary channels so the first (binary) conv layer sees a graded
# input while the whole network still computes only on bits.


def thermometer_bits(x01, width: int):
    """[0,1] intensities -> thermometer code, [..., width] {0,1} uint8.

    Bit t fires iff x >= (t+1)/(width+1): the code is monotone (all ones
    below the fill level, zeros above), so the XNOR-popcount dot of two
    codes is monotone in |x - y| — Hamming distance between codes equals
    the quantized intensity gap, which is exactly the semantics the
    Hamming-tolerant CAM search expects.  width=1 reduces to the plain
    x >= 0.5 sign binarization (`data.synthetic.binarize_images`); an
    all-zero image encodes to all-zero bits (logical -1).
    """
    if width < 1:
        raise ValueError(f"thermometer width must be >= 1, got {width}")
    x = jnp.asarray(x01)
    thr = (jnp.arange(width, dtype=jnp.float32) + 1.0) / (width + 1.0)
    return (x[..., None] >= thr).astype(jnp.uint8)


def thermometer_decode(bits):
    """Thermometer code -> intensity estimate in [0,1] (level midpoint).

    Inverse of `thermometer_bits` up to quantization: with fill level
    k = sum(bits) of width T, x is known to lie in [k/(T+1), (k+1)/(T+1))
    (clamped at the top); the midpoint (k + 0.5)/(T + 1) minimizes the
    worst-case round-trip error of 0.5/(T+1).
    """
    bits = jnp.asarray(bits)
    width = bits.shape[-1]
    k = bits.astype(jnp.int32).sum(-1).astype(jnp.float32)
    return (k + 0.5) / (width + 1.0)


def bitplane_bits(x01, width: int):
    """[0,1] intensities -> binary expansion, [..., width] {0,1} uint8.

    Quantizes to round(x * (2^width - 1)) and emits the bit planes
    LSB-first (bit t has weight 2^t).  Denser than thermometer (width
    bits give 2^width levels vs width+1) but NOT Hamming-faithful: an
    XNOR-popcount dot weighs the MSB plane the same as the LSB plane, so
    HD between codes is not monotone in |x - y| (DESIGN.md §10 records
    the tradeoff).  Round-trips exactly on the 2^width-level grid
    (`bitplane_decode`).
    """
    if width < 1:
        raise ValueError(f"bit-plane width must be >= 1, got {width}")
    levels = (1 << width) - 1
    q = jnp.round(jnp.asarray(x01, jnp.float32) * levels).astype(jnp.uint32)
    shifts = jnp.arange(width, dtype=jnp.uint32)
    return ((q[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.uint8)


def bitplane_decode(bits):
    """Bit planes (LSB-first) -> intensity in [0,1]; exact on the grid."""
    bits = jnp.asarray(bits)
    width = bits.shape[-1]
    weights = (1 << jnp.arange(width, dtype=jnp.int32)).astype(jnp.float32)
    levels = float((1 << width) - 1)
    return (bits.astype(jnp.float32) * weights).sum(-1) / levels


@dataclasses.dataclass(frozen=True)
class InputEncoding:
    """How raw [0,1] pixels become the binary input channels of a CNN.

    kind  : "thermometer" (Hamming-faithful, width+1 levels — the
            default), "bitplane" (2^width levels, not Hamming-faithful),
            or "sign" (width must be 1; plain x >= 0.5).
    width : binary channels emitted per pixel (= C_in of the first conv
            layer).

    `encode_bits` maps [..., H, W] (or any shape) intensities to
    [..., width] {0,1} bits; `encode_pm1` maps to the ±1 domain the
    float oracles consume.  `encode_image_bits` / `encode_image_pm1`
    take [..., H, W, C] images and give [..., H, W, C * width] channels,
    channel-major: channel c's code bit t is input channel c * width + t.
    All are deterministic and jit-safe.
    """

    kind: str = "thermometer"
    width: int = 8

    def __post_init__(self):
        if self.kind not in ("thermometer", "bitplane", "sign"):
            raise ValueError(f"unknown input encoding kind {self.kind!r}")
        if self.kind == "sign" and self.width != 1:
            raise ValueError("sign encoding is width-1 by definition")
        if self.width < 1:
            raise ValueError(f"encoding width must be >= 1: {self.width}")

    def encode_bits(self, x01):
        """[0,1] intensities [...] -> {0,1} uint8 bits [..., width]."""
        if self.kind == "bitplane":
            return bitplane_bits(x01, self.width)
        if self.kind == "sign":
            return (jnp.asarray(x01)[..., None] >= 0.5).astype(jnp.uint8)
        return thermometer_bits(x01, self.width)

    def encode_pm1(self, x01, dtype=jnp.float32):
        """[0,1] intensities [...] -> ±1 values [..., width]."""
        return from_bits(self.encode_bits(x01), dtype)

    def encode_image_bits(self, img01):
        """[0,1] images [..., H, W, C] -> {0,1} uint8 [..., H, W, C*width]."""
        bits = self.encode_bits(img01)
        return bits.reshape(*bits.shape[:-2], -1)

    def encode_image_pm1(self, img01, dtype=jnp.float32):
        """[0,1] images [..., H, W, C] -> ±1 values [..., H, W, C*width]."""
        return from_bits(self.encode_image_bits(img01), dtype)


def random_pm1(key, shape, dtype=jnp.float32):
    """Uniform random ±1 array (fair coin per element)."""
    return from_bits(jax.random.bernoulli(key, 0.5, shape), dtype)


def np_pack_bits(bits: np.ndarray) -> np.ndarray:
    """NumPy twin of pack_bits (for host-side dataset/CAM construction)."""
    *lead, k = bits.shape
    kw = packed_width(k)
    pad = kw * WORD - k
    if pad:
        bits = np.pad(bits, [(0, 0)] * len(lead) + [(0, pad)])
    bits = bits.reshape(*lead, kw, WORD).astype(np.uint64)
    return (bits << np.arange(WORD, dtype=np.uint64)).sum(-1).astype(np.uint32)
