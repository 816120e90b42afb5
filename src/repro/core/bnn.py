"""Binary MLP: training (latent weights + sign-STE + batch-norm) and
deployment (BN folding into CAM bias cells) — paper Eqs. (1)-(4).

Training follows BinaryConnect/XNOR-Net practice:
  * latent real-valued weights, binarized with sign() on the forward pass,
    straight-through (clipped) estimator on the backward pass;
  * activations binarized the same way between layers;
  * batch normalization after every binary dot product (Eq. 2) — essential
    so activations use both +1 and -1 (paper Sec. II-B);
  * cross-entropy on full-precision logits of the *output* dot product
    (training only; the deployed network never computes these logits —
    that is exactly what Algorithm 1 replaces).

Deployment (`fold`) collapses each BN into an integer constant C_j
(Eq. 3) and emits binary weight rows + C_j for the CAM mapper:

    BN(y) >= 0  <=>  gamma * (y - mu)/sigma + beta >= 0
                <=>  sign(gamma) * y >= sign(gamma) * (mu - beta*sigma/gamma)
   flip rows where gamma < 0 (W'_j = -W_j makes y' = -y), then
    X^{l+1} = sign(y' + C_j),   C_j = round(beta*sigma/|gamma| - mu')

so the deployed layer is exactly Eq. (3): sign(POPCOUNT(XNOR(W,x)) + C).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binarize import from_bits, sign_ste


Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    """Binary MLP hyperparameters (paper Sec. V-A models by default)."""

    layer_sizes: Sequence[int] = (784, 128, 10)  # MNIST: 784 -> 128 -> 10
    bn_eps: float = 1e-5
    bn_momentum: float = 0.9
    # number of CAM bias cells appended per row at deployment; bounds |C_j|
    bias_cells: int = 64

    @property
    def n_layers(self) -> int:
        """Number of weight layers (FC transitions)."""
        return len(self.layer_sizes) - 1


def init_params(key: jax.Array, cfg: MLPConfig, dtype=jnp.float32) -> Params:
    """Glorot-uniform latent weights + identity BN, running stats at (0,1)."""
    params: Params = {"layers": []}
    for i in range(cfg.n_layers):
        fan_in, fan_out = cfg.layer_sizes[i], cfg.layer_sizes[i + 1]
        key, sub = jax.random.split(key)
        lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
        params["layers"].append(
            {
                "w": jax.random.uniform(
                    sub, (fan_in, fan_out), dtype, minval=-lim, maxval=lim
                ),
                "gamma": jnp.ones((fan_out,), dtype),
                "beta": jnp.zeros((fan_out,), dtype),
                "mean": jnp.zeros((fan_out,), dtype),
                "var": jnp.ones((fan_out,), dtype),
            }
        )
    return params


def _bn_train(y, layer, eps, momentum):
    mu = jnp.mean(y, axis=0)
    var = jnp.var(y, axis=0)
    y_hat = (y - mu) / jnp.sqrt(var + eps)
    out = layer["gamma"] * y_hat + layer["beta"]
    new_stats = {
        "mean": momentum * layer["mean"] + (1 - momentum) * mu,
        "var": momentum * layer["var"] + (1 - momentum) * var,
    }
    return out, new_stats


def _bn_eval(y, layer, eps):
    y_hat = (y - layer["mean"]) / jnp.sqrt(layer["var"] + eps)
    return layer["gamma"] * y_hat + layer["beta"]


def forward(
    params: Params,
    x_pm1: jax.Array,
    cfg: MLPConfig,
    *,
    train: bool = False,
):
    """Forward pass on +-1 inputs.

    Returns (logits, new_params): full-precision post-BN logits of the last
    layer (training/eval criterion only) and BN-stat-updated params when
    `train=True` (otherwise params returned unchanged).
    """
    h = x_pm1
    new_layers = []
    for i, layer in enumerate(params["layers"]):
        wb = sign_ste(layer["w"])
        y = h @ wb  # binary dot product (+-1 domain); POPCOUNT equivalent
        if train:
            y, stats = _bn_train(y, layer, cfg.bn_eps, cfg.bn_momentum)
            new_layers.append({**layer, **stats})
        else:
            y = _bn_eval(y, layer, cfg.bn_eps)
            new_layers.append(layer)
        if i < cfg.n_layers - 1:
            h = sign_ste(y)  # binary activation between layers
    return y, {**params, "layers": new_layers}


def loss_fn(params: Params, x_pm1, labels, cfg: MLPConfig):
    """Cross-entropy on the (training-only) full-precision logits."""
    logits, new_params = forward(params, x_pm1, cfg, train=True)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    return nll, new_params


@dataclasses.dataclass(frozen=True)
class FoldedLayer:
    """Deployment form of one binary layer: Eq. (3) data.

    weights_pm1 : [out, in] +-1 rows (note: transposed to row-per-neuron)
    c           : [out] integer BN constants C_j
    """

    weights_pm1: np.ndarray
    c: np.ndarray

    @property
    def n_out(self) -> int:
        """Output neurons (CAM rows)."""
        return self.weights_pm1.shape[0]

    @property
    def n_in(self) -> int:
        """Input bits per row (the XNOR-popcount dot width)."""
        return self.weights_pm1.shape[1]


def parity_adjust_c(c: np.ndarray, n_in: int, bias_cells: int) -> np.ndarray:
    """Clip C_j to the bias-cell budget with dead-zone-free parity.

    y = <W_j, x> has the parity of n_in, so sign(y + C) has a dead zone
    (y + C == 0) unless C has the opposite parity.  Nudging C up by one
    is exactly decision-preserving on the even grid
    (y + C >= 0  <=>  y + C + 1 > 0); clipping can land back on the
    dead-zone parity only at the bounds, where we step one inward.
    Shared by `fold` and the benchmark/test folded-net constructors.
    """
    c = np.asarray(c, np.int64)
    c = np.where((c + n_in) % 2 == 0, c + 1, c)
    c = np.clip(c, -bias_cells, bias_cells)
    return np.where((c + n_in) % 2 == 0, c - np.sign(c).astype(c.dtype), c)


def random_folded(cfg: MLPConfig, seed: int = 0,
                  cmax: int = 40) -> list[FoldedLayer]:
    """An untrained deployed MLP: random ±1 weights, fold-style C_j.

    The MLP twin of `convnet.random_folded_cnn`, for bit-exactness
    checks and benchmarks that need shapes and semantics, not accuracy.
    """
    rng = np.random.default_rng(seed)
    layers = []
    sizes = cfg.layer_sizes
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        c = parity_adjust_c(
            rng.integers(-cmax, cmax + 1, n_out), n_in, cfg.bias_cells
        )
        layers.append(FoldedLayer(
            weights_pm1=rng.choice([-1, 1], (n_out, n_in)).astype(np.int8),
            c=c,
        ))
    return layers


def fold(params: Params, cfg: MLPConfig) -> list[FoldedLayer]:
    """Collapse trained BN into integer C_j per neuron (Eq. 3). Numpy-side."""
    folded = []
    for layer in params["layers"]:
        w = np.asarray(jnp.sign(layer["w"]))
        w = np.where(w == 0, 1.0, w).T  # [out, in], sign(0) -> +1
        gamma = np.asarray(layer["gamma"], np.float64)
        beta = np.asarray(layer["beta"], np.float64)
        mu = np.asarray(layer["mean"], np.float64)
        sigma = np.sqrt(np.asarray(layer["var"], np.float64) + cfg.bn_eps)
        # BN(y) >= 0 <=> sgn(g)*y >= sgn(g)*(mu - beta*sigma/gamma)
        flip = gamma < 0
        w = np.where(flip[:, None], -w, w)
        thresh = mu - beta * sigma / np.where(gamma == 0, 1e-12, gamma)
        thresh = np.where(flip, -thresh, thresh)
        c = np.round(-thresh).astype(np.int64)
        # C_j realized with cfg.bias_cells CAM cells: clip and match parity
        # of the dot product so sign(y + C) has no dead zone
        c = parity_adjust_c(c, w.shape[1], cfg.bias_cells)
        folded.append(FoldedLayer(weights_pm1=w.astype(np.int8), c=c))
    return folded


def folded_forward_exact(
    folded: Sequence[FoldedLayer], x_pm1: jax.Array
) -> jax.Array:
    """Eq. (3) reference semantics of the deployed net (digital oracle).

    Runs every layer as sign(W x + C); returns the *integer pre-sign* of
    the final layer (W_L h + C_L) — the quantity whose argmax Algorithm 1
    recovers through binary votes. Used as the oracle in tests/benchmarks.
    """
    h = x_pm1.astype(jnp.float32)
    for i, layer in enumerate(folded):
        w = jnp.asarray(layer.weights_pm1, jnp.float32)
        c = jnp.asarray(layer.c, jnp.float32)
        y = h @ w.T + c
        if i < len(folded) - 1:
            h = jnp.where(y >= 0, 1.0, -1.0)
    return y


def train_mlp(
    key: jax.Array,
    cfg: MLPConfig,
    train_x: np.ndarray,
    train_y: np.ndarray,
    *,
    epochs: int = 10,
    batch: int = 128,
    lr: float = 1e-3,
    weight_decay: float = 0.0,
    verbose: bool = False,
) -> Params:
    """Adam on latent weights with [-1, 1] latent clipping (BinaryConnect)."""
    params = init_params(key, cfg)
    flat, treedef = jax.tree_util.tree_flatten(params)
    m = [jnp.zeros_like(x) for x in flat]
    v = [jnp.zeros_like(x) for x in flat]

    grad_fn = jax.jit(
        lambda p, x, y: jax.grad(loss_fn, has_aux=True)(p, x, y, cfg)
    )

    @jax.jit
    def adam_update(flat, m, v, gflat, t):
        b1, b2, eps = 0.9, 0.999, 1e-8
        out_f, out_m, out_v = [], [], []
        for x, mi, vi, g in zip(flat, m, v, gflat):
            mi = b1 * mi + (1 - b1) * g
            vi = b2 * vi + (1 - b2) * g * g
            mh = mi / (1 - b1**t)
            vh = vi / (1 - b2**t)
            x = x - lr * mh / (jnp.sqrt(vh) + eps)
            out_f.append(x)
            out_m.append(mi)
            out_v.append(vi)
        return out_f, out_m, out_v

    n = train_x.shape[0]
    steps_per_epoch = max(n // batch, 1)
    t = 0
    rng = np.random.default_rng(0)
    for epoch in range(epochs):
        perm = rng.permutation(n)
        for s in range(steps_per_epoch):
            idx = perm[s * batch : (s + 1) * batch]
            xb = jnp.asarray(train_x[idx])
            yb = jnp.asarray(train_y[idx])
            grads, new_params = grad_fn(params, xb, yb)
            # BN running stats come back through the aux output
            params = new_params
            gflat = jax.tree_util.tree_leaves(grads)
            flat = jax.tree_util.tree_leaves(params)
            t += 1
            flat, m, v = adam_update(flat, m, v, gflat, t)
            # clip latent weights to [-1, 1] (BinaryConnect); BN params free
            params = jax.tree_util.tree_unflatten(treedef, flat)
            for layer in params["layers"]:
                layer["w"] = jnp.clip(layer["w"], -1.0, 1.0)
        if verbose:
            logits, _ = forward(params, jnp.asarray(train_x[:2048]), cfg)
            acc = float(
                (jnp.argmax(logits, -1) == jnp.asarray(train_y[:2048])).mean()
            )
            print(f"  epoch {epoch + 1}/{epochs}: train-acc(sample)={acc:.4f}")
    return params


def eval_accuracy(params: Params, cfg: MLPConfig, x, y, topk=(1,)) -> dict:
    """Top-k accuracy of the full-precision-logit software path."""
    logits, _ = forward(params, jnp.asarray(x), cfg)
    order = jnp.argsort(-logits, axis=-1)
    out = {}
    yj = jnp.asarray(y)[:, None]
    for k in topk:
        out[f"top{k}"] = float((order[:, :k] == yj).any(-1).mean())
    return out
