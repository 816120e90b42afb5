"""End-to-end-binary CNN: training (sign-STE conv + batch-norm) and
deployment folding for the conv pipeline.

The paper's central claim is *end-to-end* binarization: typical binary
CNNs keep the input layer in full precision, PiC-BNN binarizes
everything.  This module carries the conv analogue of `core/bnn.py`:

  * the INPUT layer is binary too — each channel of the raw [0,1]
    pixels passes through a `binarize.InputEncoding` (thermometer by
    default) into `width` binary channels before the first conv;
  * conv layers train with latent real weights + sign-STE + per-channel
    batch norm, exactly the BinaryConnect recipe `bnn.py` uses for FC
    layers;
  * `fold_cnn` collapses each conv BN into an integer constant C_o
    (Eq. 3 per output channel) and emits `FoldedConvLayer` rows that
    `kernels/fused_conv.py` runs, followed by folded FC layers for the
    MLP head — one flat list that `pipeline.compile_pipeline` compiles
    end to end.

Spatial semantics: k x k convolutions with integer stride, VALID or
SAME zero padding (a pad position contributes 0 to the dot, exactly as
a zero-padded ±1 conv), optionally followed by a p x p / p max-pool.
The pool sits between the conv and the batch norm, as in BinaryNet
(Courbariaux et al. 2016), so it needs no majority unit: after the fold
the pooled bit of a channel whose BN scale is positive is the OR of the
window's sign bits, and the AND for a negative scale, whose row the fold
negates (`FoldedConvLayer.pool_sign`).  Layouts are documented in
DESIGN.md §10.

Residual layers (Bi-Real Net, Liu et al. 2018): a `residual` conv
writes an int32 residual stream x instead of a ±1 map,

    x' = a * conv(sign(x)) + C + shortcut(x),   a = s * m per channel

with s = ±1 the sign of the folded BN scale (in the rows, as above) and
m >= 1 its integer magnitude (`FoldedConvLayer.residual`).  The
shortcut is "none" (the layer starts the stream: a stem), "identity"
(x itself), "down" (Bi-Real's downsample, a * conv1x1(sign(
sumpool_stride(x))) + C, `FoldedConvLayer.down`), "avg" (ReActNet's
AvgPool 2x2 of a stride-2 block, floor(sumpool_2x2(x) / 4)) or "dup"
(ReActNet's reduction block, the stream concatenated with itself on
channels, for a c -> 2c conv).  A residual layer's pool sums the stream
(a global pool at the last layer), and whatever reads the stream, the
next conv or the FC stage, reads its sign.

ReActNet (Liu et al. 2020) moves the sign and the stream per channel
with integer parameters: a residual layer's `alpha` makes it read
sign(x - alpha) (RSign, one threshold per input channel), and its
`rprelu` bends its output after the shortcut's add (RPReLU, `RPReLU`).
Training (`cnn_forward`, `fold_cnn`) covers the plain chain only.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bnn import FoldedLayer, parity_adjust_c
from repro.core.binarize import InputEncoding, sign_ste

Params = dict[str, Any]


PADDINGS = ("valid", "same")
#: what a residual layer adds onto its conv: nothing (it starts the
#: stream), the stream itself, Bi-Real's downsample of it, its 2x2
#: floor-average (a stride-2 layer) or the stream twice on channels (a
#: layer of twice the width)
SHORTCUTS = ("none", "identity", "down", "avg", "dup")
#: the shortcuts that carry the incoming stream on: each keeps its
#: values, so the stream goes on as one tensor
CARRIED = ("identity", "avg", "dup")


def conv_pads(side: int, k: int, stride: int, padding: str) -> tuple:
    """(low, high) zero padding of each spatial axis: none for "valid";
    for "same" the output side is ceil(side / stride) and the padding is
    split as XLA's SAME splits it (the odd one on the high side)."""
    if padding not in PADDINGS:
        raise ValueError(f"padding {padding!r} not in {PADDINGS}")
    if padding == "valid":
        return 0, 0
    out = -(-side // stride)
    total = max((out - 1) * stride + k - side, 0)
    return total // 2, total - total // 2


def conv_out_side(side: int, k: int, stride: int, padding: str,
                  pool: int) -> tuple[int, int]:
    """(conv output side, side after the pool) of a square `side` input."""
    lo, hi = conv_pads(side, k, stride, padding)
    if side + lo + hi < k:
        raise ValueError(f"input side {side} < kernel {k}")
    conv = (side + lo + hi - k) // stride + 1
    if conv < pool:
        raise ValueError(f"conv output side {conv} < pool {pool}")
    return conv, conv // pool


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """One binary conv layer: k x k window, c_out filters, stride,
    "valid" or "same" zero padding, then a pool x pool / pool max-pool
    (pool 1: none).  A `residual` layer writes the int32 residual stream
    (module doc) with its `shortcut` added, and its pool sums; a `react`
    layer (ReActNet) reads the stream through RSign thresholds and
    applies RPReLU after the add (`FoldedConvLayer.alpha`, `.rprelu`)."""

    k: int
    c_out: int
    stride: int = 1
    padding: str = "valid"
    pool: int = 1
    residual: bool = False
    shortcut: str = "none"
    react: bool = False

    def __post_init__(self):
        if (self.k < 1 or self.c_out < 1 or self.stride < 1
                or self.pool < 1 or self.padding not in PADDINGS
                or self.shortcut not in SHORTCUTS
                or (self.shortcut == "avg" and self.stride != 2)
                or ((self.shortcut != "none" or self.react)
                    and not self.residual)):
            raise ValueError(f"bad ConvSpec {self}")

    def conv_side(self, side: int) -> int:
        """Conv output side (before the pool) for a square `side` input."""
        return conv_out_side(side, self.k, self.stride, self.padding,
                             self.pool)[0]

    def out_side(self, side: int) -> int:
        """Output side after the pool for a square `side` input."""
        return conv_out_side(side, self.k, self.stride, self.padding,
                             self.pool)[1]


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    """End-to-end-binary CNN hyperparameters.

    side      : square input image side
    channels  : input channels per pixel (n_in = side * side * channels
                raw pixels, HWC order)
    encoding  : binary input layer (each [0,1] channel value ->
                `encoding.width` binary channels; the paper's end-to-end
                claim)
    conv      : conv stack (`ConvSpec`: padding, stride, pool)
    hidden    : FC widths between the flatten and the output layer
    n_classes : output classes (the CAM ensemble head rows)
    """

    side: int = 28
    channels: int = 1
    encoding: InputEncoding = InputEncoding("thermometer", 8)
    conv: Sequence[ConvSpec] = (ConvSpec(3, 32, 2), ConvSpec(3, 32, 2))
    hidden: Sequence[int] = (128,)
    n_classes: int = 10
    bn_eps: float = 1e-5
    bn_momentum: float = 0.9
    bias_cells: int = 64

    @property
    def n_in(self) -> int:
        """Raw pixel count the pipeline/serving layer sees."""
        return self.side * self.side * self.channels

    def feature_sides(self) -> list[int]:
        """Feature-map side after the input and after each conv layer."""
        sides = [self.side]
        for spec in self.conv:
            sides.append(spec.out_side(sides[-1]))
        return sides

    def feature_channels(self) -> list[int]:
        """Channel count entering each conv layer (+ the final one)."""
        return ([self.encoding.width * self.channels]
                + [s.c_out for s in self.conv])

    @property
    def flat_features(self) -> int:
        """Logical bits entering the MLP stage (final side^2 * c_out)."""
        return self.feature_sides()[-1] ** 2 * self.feature_channels()[-1]

    @property
    def fc_sizes(self) -> tuple[int, ...]:
        """(flat, *hidden, n_classes) — the MLP-stage layer sizes."""
        return (self.flat_features, *self.hidden, self.n_classes)


@dataclasses.dataclass(frozen=True)
class RPReLU:
    """ReActNet's RPReLU in integers, per output channel o:

        f(y) = y - gamma_o + zeta_o                     y >= gamma_o
               floor(q_o * (y - gamma_o) / 8) + zeta_o  otherwise

    a PReLU of slope beta = q / 8 (q in 0..8) between two learnt shifts.
    gamma, q, zeta: [c_out] integers.  With 0 <= beta <= 1, |f(y)| <=
    |y| + |gamma| + |zeta|."""

    gamma: np.ndarray
    q: np.ndarray
    zeta: np.ndarray

    def __post_init__(self):
        shapes = {np.shape(a) for a in (self.gamma, self.q, self.zeta)}
        if len(shapes) != 1 or len(shapes.pop()) != 1:
            raise ValueError("RPReLU's gamma, q, zeta must be [c_out] each")
        if not ((0 <= np.asarray(self.q)) & (np.asarray(self.q) <= 8)).all():
            raise ValueError("RPReLU's slope numerator q must be in 0..8")

    @property
    def c_out(self) -> int:
        """Output channels it bends."""
        return len(self.q)


@dataclasses.dataclass(frozen=True)
class FoldedConvLayer:
    """Deployment form of one binary conv layer (Eq. 3 per channel).

    weights_pm1 : [c_out, k, k, c_in] ±1 filters (one CAM row per output
                  channel; row bits ordered tap-major (dy, dx, c),
                  the CAM row of one patch)
    c           : [c_out] integer BN constants, parity-adjusted so
                  sign(dot + C) has no dead zone (bnn.parity_adjust_c)
    stride      : spatial stride
    padding     : "valid" or "same" (zero padding; pads add 0 to a dot)
    pool        : max-pool window and stride (1: no pool)
    pool_sign   : [c_out] ±1, the sign s of each channel's folded BN
                  scale (None: all +1).  The fold negates the rows of
                  s = -1 channels, so a pooled channel's bit is the OR of
                  its window's sign(dot + C) bits for s = +1 and the AND
                  for s = -1: sign(s * maxpool(y) + C) with y the conv
                  output of the unnegated rows.  Unused without a pool.
    residual    : [c_out] integer magnitudes m >= 1 of the folded BN
                  scale (None: a plain layer, sign(dot + C)).  Set, the
                  layer writes the residual stream: m * dot + C + the
                  shortcut, dot taken with the rows (which hold s), and
                  its pool sums the stream; no pool_sign.
    shortcut    : `SHORTCUTS` (only "none" without `residual`):
                  "none", "identity", "down", "avg" (stride 2, c_out =
                  c_in: floor(sumpool_2x2(x) / 4)) or "dup" (c_out =
                  2 * c_in: concat(x, x) on channels)
    down        : the "down" shortcut's 1 x 1 residual conv (c_in ->
                  c_out, stride 1, shortcut "none"), which reads
                  sign(sumpool(x)) over stride x stride windows of the
                  incoming stream x; None for every other shortcut
    alpha       : [c_in] integer RSign thresholds of a residual layer
                  that reads a stream (None: 0): its conv reads
                  sign(x - alpha), +1 where x >= alpha
    rprelu      : `RPReLU` of a residual layer (None: the identity),
                  applied per channel after the shortcut's add and
                  before the pool
    """

    weights_pm1: np.ndarray
    c: np.ndarray
    stride: int = 1
    padding: str = "valid"
    pool: int = 1
    pool_sign: np.ndarray | None = None
    residual: np.ndarray | None = None
    shortcut: str = "none"
    down: "FoldedConvLayer | None" = None
    alpha: np.ndarray | None = None
    rprelu: RPReLU | None = None

    def __post_init__(self):
        if self.padding not in PADDINGS or self.pool < 1:
            raise ValueError(f"bad padding {self.padding!r} / pool "
                             f"{self.pool}")
        if self.pool_sign is not None and (
                np.shape(self.pool_sign) != (self.c_out,)
                or not np.isin(self.pool_sign, (-1, 1)).all()):
            raise ValueError("pool_sign must be [c_out] of ±1")
        if self.shortcut not in SHORTCUTS:
            raise ValueError(f"shortcut {self.shortcut!r} not in "
                             f"{SHORTCUTS}")
        if self.residual is None:
            if (self.shortcut != "none" or self.down is not None
                    or self.alpha is not None or self.rprelu is not None):
                raise ValueError("a shortcut, RSign or RPReLU needs a "
                                 "residual layer")
            return
        if self.alpha is not None and np.shape(self.alpha) != (self.c_in,):
            raise ValueError("alpha must be [c_in] integers")
        if self.rprelu is not None and self.rprelu.c_out != self.c_out:
            raise ValueError("rprelu must be [c_out] per channel")
        if self.shortcut == "avg" and (self.stride != 2
                                       or self.c_out != self.c_in):
            raise ValueError('shortcut "avg" needs stride 2 and c_out == '
                             'c_in')
        if self.shortcut == "dup" and self.c_out != 2 * self.c_in:
            raise ValueError('shortcut "dup" needs c_out == 2 * c_in')
        if (np.shape(self.residual) != (self.c_out,)
                or (np.asarray(self.residual) < 1).any()):
            raise ValueError("residual must be [c_out] integers >= 1")
        if self.pool_sign is not None:
            raise ValueError("a residual layer's pool sums: no pool_sign")
        if (self.shortcut == "down") != (self.down is not None):
            raise ValueError('down= goes with shortcut="down" alone')
        d = self.down
        if d is not None and (
                d.residual is None or d.shortcut != "none" or d.k != 1
                or d.stride != 1 or d.pool != 1
                or (d.c_in, d.c_out) != (self.c_in, self.c_out)):
            raise ValueError("down must be a 1x1 stride-1 residual conv "
                             f"{self.c_in} -> {self.c_out} with no "
                             "shortcut or pool")

    @property
    def pool_or(self) -> np.ndarray:
        """[c_out] bool: True where the pool ORs its sign bits (s = +1),
        False where it ANDs them (s = -1)."""
        if self.pool_sign is None:
            return np.ones(self.c_out, bool)
        return np.asarray(self.pool_sign) > 0

    @property
    def c_out(self) -> int:
        """Output channels (CAM rows / bits produced per position)."""
        return self.weights_pm1.shape[0]

    @property
    def k(self) -> int:
        """Square kernel side."""
        return self.weights_pm1.shape[1]

    @property
    def c_in(self) -> int:
        """Input channels per tap."""
        return self.weights_pm1.shape[3]

    @property
    def n_bits(self) -> int:
        """Logical dot width: k * k * c_in bits per patch."""
        return self.k * self.k * self.c_in


def init_cnn_params(key: jax.Array, cfg: CNNConfig,
                    dtype=jnp.float32) -> Params:
    """Glorot latent conv filters + FC weights, identity batch norm."""
    params: Params = {"conv": [], "fc": []}
    c_in = cfg.feature_channels()[0]
    for spec in cfg.conv:
        key, sub = jax.random.split(key)
        fan_in = spec.k * spec.k * c_in
        fan_out = spec.k * spec.k * spec.c_out
        lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
        params["conv"].append({
            "w": jax.random.uniform(
                sub, (spec.k, spec.k, c_in, spec.c_out), dtype,
                minval=-lim, maxval=lim,
            ),
            "gamma": jnp.ones((spec.c_out,), dtype),
            "beta": jnp.zeros((spec.c_out,), dtype),
            "mean": jnp.zeros((spec.c_out,), dtype),
            "var": jnp.ones((spec.c_out,), dtype),
        })
        c_in = spec.c_out
    sizes = cfg.fc_sizes
    for i in range(len(sizes) - 1):
        key, sub = jax.random.split(key)
        lim = float(np.sqrt(6.0 / (sizes[i] + sizes[i + 1])))
        params["fc"].append({
            "w": jax.random.uniform(
                sub, (sizes[i], sizes[i + 1]), dtype,
                minval=-lim, maxval=lim,
            ),
            "gamma": jnp.ones((sizes[i + 1],), dtype),
            "beta": jnp.zeros((sizes[i + 1],), dtype),
            "mean": jnp.zeros((sizes[i + 1],), dtype),
            "var": jnp.ones((sizes[i + 1],), dtype),
        })
    return params


def _bn(y, layer, eps, momentum, train: bool, axes):
    if train:
        mu = jnp.mean(y, axis=axes)
        var = jnp.var(y, axis=axes)
        stats = {
            "mean": momentum * layer["mean"] + (1 - momentum) * mu,
            "var": momentum * layer["var"] + (1 - momentum) * var,
        }
    else:
        mu, var = layer["mean"], layer["var"]
        stats = {}
    y_hat = (y - mu) / jnp.sqrt(var + eps)
    return layer["gamma"] * y_hat + layer["beta"], stats


def cnn_forward(params: Params, x01: jax.Array, cfg: CNNConfig, *,
                train: bool = False):
    """Forward pass on raw [0,1] pixels [B, side*side*channels] (HWC).

    The input layer is BINARY: pixels pass through `cfg.encoding` into
    ±1 channels before the first conv — no full-precision input layer
    anywhere.  Each conv is followed by its max-pool (if any), then
    batch norm and sign (BinaryNet's order).  Returns (logits,
    new_params) like `bnn.forward`: full-precision post-BN logits of the
    output layer (training criterion only; deployment replaces them with
    Algorithm-1 votes) and BN-stat-updated params when `train=True`.
    """
    _plain_chain(cfg)
    b = x01.shape[0]
    h = cfg.encoding.encode_image_pm1(
        jnp.asarray(x01).reshape(b, cfg.side, cfg.side, cfg.channels)
    )  # [B, H, W, C*E] ±1 — the binary input layer
    new_conv = []
    for layer, spec in zip(params["conv"], cfg.conv):
        wb = sign_ste(layer["w"])  # [k, k, c_in, c_out] ±1
        pads = conv_pads(h.shape[1], spec.k, spec.stride, spec.padding)
        y = jax.lax.conv_general_dilated(
            h, wb, window_strides=(spec.stride, spec.stride),
            padding=(pads, pads),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if spec.pool > 1:
            win = (1, spec.pool, spec.pool, 1)
            y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, win, win,
                                      "VALID")
        y, stats = _bn(y, layer, cfg.bn_eps, cfg.bn_momentum, train,
                       axes=(0, 1, 2))
        new_conv.append({**layer, **stats})
        h = sign_ste(y)
    h = h.reshape(b, -1)  # NHWC flatten: logical (y, x, channel) order
    new_fc = []
    n_fc = len(params["fc"])
    for i, layer in enumerate(params["fc"]):
        wb = sign_ste(layer["w"])
        y = h @ wb
        y, stats = _bn(y, layer, cfg.bn_eps, cfg.bn_momentum, train,
                       axes=(0,))
        new_fc.append({**layer, **stats})
        if i < n_fc - 1:
            h = sign_ste(y)
    return y, {"conv": new_conv, "fc": new_fc}


def _plain_chain(cfg: CNNConfig) -> None:
    if any(spec.residual for spec in cfg.conv):
        raise ValueError("training and folding cover plain conv chains; "
                         "residual nets deploy from folded layers "
                         "(random_folded_cnn)")


def cnn_loss(params: Params, x01, labels, cfg: CNNConfig):
    """Cross-entropy on the (training-only) full-precision logits."""
    logits, new_params = cnn_forward(params, x01, cfg, train=True)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    return nll, new_params


def _fold_bn(w_rows: np.ndarray, layer, eps: float, n_bits: int,
             bias_cells: int):
    """Shared Eq.-3 BN collapse: ±1 rows [out, bits] + BN -> (rows, C).

    Same algebra as `bnn.fold`: flip rows where gamma < 0, then
    C = round(beta*sigma/|gamma| - mu'), parity-adjusted against the
    dot width so sign(dot + C) never hits the dead zone.  Also returns
    the sign of each gamma (+1 for gamma >= 0): the flipped rows' pool
    polarity.
    """
    gamma = np.asarray(layer["gamma"], np.float64)
    beta = np.asarray(layer["beta"], np.float64)
    mu = np.asarray(layer["mean"], np.float64)
    sigma = np.sqrt(np.asarray(layer["var"], np.float64) + eps)
    flip = gamma < 0
    w_rows = np.where(flip.reshape((-1,) + (1,) * (w_rows.ndim - 1)),
                      -w_rows, w_rows)
    thresh = mu - beta * sigma / np.where(gamma == 0, 1e-12, gamma)
    thresh = np.where(flip, -thresh, thresh)
    c = parity_adjust_c(np.round(-thresh).astype(np.int64), n_bits,
                        bias_cells)
    return w_rows.astype(np.int8), c, np.where(flip, -1, 1).astype(np.int8)


def fold_cnn(params: Params, cfg: CNNConfig) -> list:
    """Collapse trained BN into integer constants per channel/neuron.

    Returns [FoldedConvLayer, ..., FoldedLayer, ...] — the conv stack
    followed by the MLP stage, the flat graph
    `pipeline.compile_pipeline` accepts.  Conv filters are emitted as
    CAM rows [c_out, k, k, c_in] (tap-major bit order), with the layer's
    padding, pool and, for a pooled layer, each channel's pool polarity
    (the BN fold commutes with the max-pool: it is monotone in the
    pooled value); the first FC layer's n_in is `cfg.flat_features` in
    NHWC flatten order, matching the training-time reshape bit for bit.
    """
    _plain_chain(cfg)
    folded: list = []
    for layer, spec in zip(params["conv"], cfg.conv):
        w = np.asarray(jnp.sign(layer["w"]))
        w = np.where(w == 0, 1.0, w)  # sign(0) -> +1, paper's '1' coding
        # [k, k, c_in, c_out] -> rows [c_out, k, k, c_in]
        w = np.transpose(w, (3, 0, 1, 2))
        n_bits = spec.k * spec.k * w.shape[3]
        w, c, s = _fold_bn(w, layer, cfg.bn_eps, n_bits, cfg.bias_cells)
        folded.append(FoldedConvLayer(
            weights_pm1=w, c=c, stride=spec.stride, padding=spec.padding,
            pool=spec.pool, pool_sign=s if spec.pool > 1 else None))
    for layer in params["fc"]:
        w = np.asarray(jnp.sign(layer["w"]))
        w = np.where(w == 0, 1.0, w).T  # [out, in]
        w, c, _ = _fold_bn(w, layer, cfg.bn_eps, w.shape[1],
                           cfg.bias_cells)
        folded.append(FoldedLayer(weights_pm1=w, c=c))
    return folded


def train_cnn(
    key: jax.Array,
    cfg: CNNConfig,
    train_x: np.ndarray,
    train_y: np.ndarray,
    *,
    epochs: int = 6,
    batch: int = 128,
    lr: float = 1e-3,
    verbose: bool = False,
) -> Params:
    """Adam on latent weights with [-1, 1] latent clipping.

    `train_x` is RAW [0,1] pixels [N, n_in] — the binary input
    encoding happens inside the forward pass (the whole point of the
    end-to-end-binary workload).  Same BinaryConnect recipe as
    `bnn.train_mlp`; BN running stats ride back through the loss aux.
    """
    params = init_cnn_params(key, cfg)
    flat, treedef = jax.tree_util.tree_flatten(params)
    m = [jnp.zeros_like(x) for x in flat]
    v = [jnp.zeros_like(x) for x in flat]

    grad_fn = jax.jit(
        lambda p, x, y: jax.grad(cnn_loss, has_aux=True)(p, x, y, cfg)
    )

    @jax.jit
    def adam_update(flat, m, v, gflat, t):
        b1, b2, eps = 0.9, 0.999, 1e-8
        out_f, out_m, out_v = [], [], []
        for x, mi, vi, g in zip(flat, m, v, gflat):
            mi = b1 * mi + (1 - b1) * g
            vi = b2 * vi + (1 - b2) * g * g
            mh = mi / (1 - b1 ** t)
            vh = vi / (1 - b2 ** t)
            out_f.append(x - lr * mh / (jnp.sqrt(vh) + eps))
            out_m.append(mi)
            out_v.append(vi)
        return out_f, out_m, out_v

    n = train_x.shape[0]
    steps = max(n // batch, 1)
    t = 0
    rng = np.random.default_rng(0)
    for epoch in range(epochs):
        perm = rng.permutation(n)
        for s in range(steps):
            idx = perm[s * batch: (s + 1) * batch]
            grads, params = grad_fn(
                params, jnp.asarray(train_x[idx]), jnp.asarray(train_y[idx])
            )
            gflat = jax.tree_util.tree_leaves(grads)
            flat = jax.tree_util.tree_leaves(params)
            t += 1
            flat, m, v = adam_update(flat, m, v, gflat, t)
            params = jax.tree_util.tree_unflatten(treedef, flat)
            # clip ONLY the latent weights to [-1, 1] (BinaryConnect);
            # BN params and running stats must stay free — clipping them
            # would pin the running variance at 1 and corrupt every
            # eval/fold that consumes the stats (train_mlp's contract)
            for layer in params["conv"] + params["fc"]:
                layer["w"] = jnp.clip(layer["w"], -1.0, 1.0)
        if verbose:
            logits, _ = cnn_forward(params, jnp.asarray(train_x[:1024]), cfg)
            acc = float(
                (jnp.argmax(logits, -1) == jnp.asarray(train_y[:1024])).mean()
            )
            print(f"  epoch {epoch + 1}/{epochs}: train-acc(sample)={acc:.4f}")
    return params


def eval_cnn_accuracy(params: Params, cfg: CNNConfig, x01, y,
                      topk=(1,)) -> dict:
    """Top-k accuracy of the full-precision-logit software path."""
    logits, _ = cnn_forward(params, jnp.asarray(x01), cfg)
    order = jnp.argsort(-logits, axis=-1)
    yj = jnp.asarray(y)[:, None]
    return {
        f"top{k}": float((order[:, :k] == yj).any(-1).mean()) for k in topk
    }


def cnn_inference_cost(cfg: CNNConfig, n_output_passes: int = 33):
    """Table-II-style silicon cost of one CNN inference on the macro.

    Each conv layer maps its filters onto a CAM tile plan
    (`mapping.plan_layer` with row width k*k*c_in + bias cells) and is
    searched once per conv output position (before any pool), and so is
    a downsample shortcut's 1 x 1 conv; FC layers
    query once; the output
    layer sweeps `n_output_passes` thresholds.  This is what the serving
    registry reports as the silicon-equivalent throughput for CNN
    models (`PicBnnServer.register(silicon_cost=...)`).
    """
    from repro.core import mapping

    sides = cfg.feature_sides()
    chans = cfg.feature_channels()
    plans, queries = [], []
    for spec, c_in, s_in in zip(cfg.conv, chans[:-1], sides[:-1]):
        plans.append(mapping.plan_layer(
            spec.c_out, spec.k * spec.k * c_in, cfg.bias_cells
        ))
        queries.append(spec.conv_side(s_in) ** 2)
        if spec.shortcut == "down":
            plans.append(mapping.plan_layer(spec.c_out, c_in,
                                            cfg.bias_cells))
            queries.append(queries[-1])
    sizes = cfg.fc_sizes
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        plans.append(mapping.plan_layer(n_out, n_in, cfg.bias_cells))
        queries.append(1)
    return mapping.model_inference_cost(
        plans, n_output_passes, layer_queries=queries
    )


def random_folded_cnn(cfg: CNNConfig, seed: int = 0, cmax: int = 24) -> list:
    """An untrained deployed CNN with fold-style parity-adjusted C.

    The shape-and-semantics twin of the benchmarks' `random_folded` MLP
    helper: random ±1 filters/weights with valid dead-zone-free
    constants (and, for pooled layers, random ±1 pool polarities; for
    residual layers, magnitudes m in 1..4 and each downsample's 1 x 1
    conv, then for `react` layers RSign thresholds and RPReLU shifts in
    [-cmax, cmax] with q in 0..8: all drawn after everything else so
    plain unpooled nets and earlier residual nets keep their draws), for
    bit-exactness tests and throughput benchmarks that don't need a
    trained model.
    """
    rng = np.random.default_rng(seed)
    folded: list = []
    c_in = cfg.feature_channels()[0]
    for spec in cfg.conv:
        n_bits = spec.k * spec.k * c_in
        c = parity_adjust_c(
            rng.integers(-cmax, cmax + 1, spec.c_out), n_bits,
            cfg.bias_cells,
        )
        folded.append(FoldedConvLayer(
            weights_pm1=rng.choice(
                [-1, 1], (spec.c_out, spec.k, spec.k, c_in)
            ).astype(np.int8),
            c=c,
            stride=spec.stride,
            padding=spec.padding,
            pool=spec.pool,
        ))
        c_in = spec.c_out
    sizes = cfg.fc_sizes
    for i in range(len(sizes) - 1):
        c = parity_adjust_c(
            rng.integers(-cmax, cmax + 1, sizes[i + 1]), sizes[i],
            cfg.bias_cells,
        )
        folded.append(FoldedLayer(
            weights_pm1=rng.choice(
                [-1, 1], (sizes[i + 1], sizes[i])
            ).astype(np.int8),
            c=c,
        ))
    for i, spec in enumerate(cfg.conv):
        if spec.pool > 1 and not spec.residual:
            folded[i] = dataclasses.replace(
                folded[i], pool_sign=rng.choice([-1, 1], spec.c_out)
                .astype(np.int8))
    for i, spec in enumerate(cfg.conv):
        if not spec.residual:
            continue
        down = None
        if spec.shortcut == "down":
            c_in = folded[i].c_in
            down = FoldedConvLayer(
                weights_pm1=rng.choice([-1, 1], (spec.c_out, 1, 1, c_in))
                .astype(np.int8),
                c=rng.integers(-cmax, cmax + 1, spec.c_out),
                residual=rng.integers(1, 5, spec.c_out))
        folded[i] = dataclasses.replace(
            folded[i], residual=rng.integers(1, 5, spec.c_out),
            shortcut=spec.shortcut, down=down)
    for i, spec in enumerate(cfg.conv):
        if spec.react:
            folded[i] = dataclasses.replace(
                folded[i], alpha=rng.integers(-cmax, cmax + 1, folded[i].c_in),
                rprelu=RPReLU(
                    gamma=rng.integers(-cmax, cmax + 1, spec.c_out),
                    q=rng.integers(0, 9, spec.c_out),
                    zeta=rng.integers(-cmax, cmax + 1, spec.c_out)))
    return folded
