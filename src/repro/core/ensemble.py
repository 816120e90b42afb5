"""Algorithm 1 — the paper's core contribution.

The output (fully connected) layer of a classification BNN is executed
multiple times with a *varying Hamming-distance tolerance threshold* (swept
through the analog knobs V_ref / V_eval / V_st).  Each pass produces one
binary output per class ("does class j match the feature vector within
HD <= T_t ?").  The final prediction is the per-class majority (vote count)
over the passes.

Why this works (law of large numbers, Sec. IV): with thresholds swept over
{0, 2, ..., 64}, class j collects ``votes_j = #{t : HD_j <= T_t + noise}``.
In the noiseless limit votes_j = #{t : T_t >= HD_j} is strictly monotone
decreasing in HD_j, so argmax(votes) == argmin(HD) == argmax(full-precision
logit) — the FP logit ranking is recovered from purely binary measurements.
Under analog noise each vote is a Bernoulli trial with success probability
sigmoid-like in (T_t - HD_j); summing over passes concentrates the estimate
(LLN), which is what lets the silicon skip ADC/TDC readout entirely.

Execution modes:
  faithful  — 33 sequential searches, per-pass PVT noise, per-pass knob
              voltages from the behavioural device model (the silicon flow).
  fused     — beyond-paper TPU optimization: HD is computed once per
              (query, row) and compared against all T in-register; the vote
              count is materialized directly.  Bit-exact equal to `faithful`
              in the noiseless limit (tests assert this); ~33x fewer array
              reads.  `votes_fused_noisy` is the silicon-conditioned twin:
              same HD-once amortization, thresholds sampled per pass from
              the unified physics (`core/physics.SearchPhysics`) — equal to
              `faithful` in distribution (tests assert mean/variance
              agreement), bit-equal to `fused` in the NOISELESS limit.
  kernel    — the Pallas implementation of `fused` (kernels/cam_search.py).

All noisy paths draw their effective thresholds from ONE sampler
(`SearchPhysics.sample`); no noise arithmetic lives in this module
(DESIGN.md §8).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binarize
from repro.core.bnn import FoldedLayer
from repro.core.cam import CAMArray, query_with_bias, write_weights_with_bias
from repro.core.device_model import (
    AnalogParams,
    NoiseModel,
    NOISELESS,
    default_params,
    knob_schedule,
)
from repro.core.physics import SearchPhysics, achieved_sweep

# Algorithm 1 line 3: HD threshold sweep {0, 2, 4, ..., 64} -> 33 passes.
PAPER_THRESHOLDS = tuple(range(0, 65, 2))


@dataclasses.dataclass(frozen=True)
class EnsembleConfig:
    thresholds: Sequence[int] = PAPER_THRESHOLDS
    bias_cells: int = 64
    noise: NoiseModel = NOISELESS
    mode: str = "fused"  # faithful | fused | kernel
    # True: deploy the knob schedule's *achieved* calibrated tolerances
    # (what the analog knobs actually deliver, float) instead of the ideal
    # integer sweep — see build_head.
    calibrated: bool = False

    @property
    def n_passes(self) -> int:
        """Output-layer executions in the Algorithm-1 sweep."""
        return len(self.thresholds)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CAMEnsembleHead:
    """The deployed output layer: a CAM array + the threshold schedule.

    cam        : rows = classes; row = [binary weights | bias cells(C_j)]
    thresholds : int32 [n_passes] — HD tolerances swept by Algorithm 1.
                 NOTE: silicon thresholds apply to the *biased* row of width
                 n_in + bias_cells; a logical sweep {0,2,..,64} over logit
                 space maps to HD space via T_hd = (n_total - T_logit... see
                 `logit_sweep_to_hd`) — we store HD-space thresholds.
    """

    cam: CAMArray
    thresholds: jax.Array
    bias_cells: int

    def tree_flatten(self):
        """jax pytree protocol (heads pass through jit boundaries)."""
        return (self.cam, self.thresholds), (self.bias_cells,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """jax pytree protocol inverse of `tree_flatten`."""
        return cls(cam=children[0], thresholds=children[1], bias_cells=aux[0])

    @property
    def n_classes(self) -> int:
        """Classes = CAM rows of the head."""
        return self.cam.n_rows


def build_head(
    layer: FoldedLayer,
    cfg: EnsembleConfig,
) -> CAMEnsembleHead:
    """Write the folded output layer into a CAM ensemble head.

    Threshold-space note: Algorithm 1 sweeps HD tolerance {0, 2, ..., 64}.
    For a row of n_in + bias_cells total bits, the *informative* HD range
    (where class match decisions actually flip) is centered at the exact-
    majority point n_total/2 (dot = n - 2*HD, majority <=> HD <= n/2).  A
    raw absolute sweep {0..64} over a 192-bit row would never fire; we
    therefore center the paper's sweep on the majority point:
    ``T_t = n_total/2 - max(sweep)/2 + t`` — recovering exactly the paper's
    33 equispaced tolerance levels straddling the decision boundary.  This
    reading reproduces Fig. 5 (accuracy grows then saturates with pass
    count) and is recorded as an assumption in DESIGN.md.

    With ``cfg.calibrated`` the ideal integer sweep is replaced by the
    knob schedule's *achieved* tolerances (`physics.achieved_sweep`): the
    float thresholds the Table-I-calibrated analog knobs actually deliver,
    offset by the same centering.  Thresholds then carry float32 dtype;
    every consumer (fused/faithful/kernels) compares HD against them
    unchanged.
    """
    cam = write_weights_with_bias(layer.weights_pm1, layer.c, cfg.bias_cells)
    n_total = layer.n_in + cfg.bias_cells
    center = n_total // 2
    sweep = np.asarray(cfg.thresholds, np.int64)
    offset = center - sweep.max() // 2
    if cfg.calibrated:
        # achieved_sweep targets the equispaced linspace(0, max, P) —
        # the paper's sweep; anything else would silently deploy
        # unrelated operating points
        if not np.array_equal(
            sweep, np.linspace(0, sweep.max(), len(sweep)).round()
        ):
            raise ValueError(
                "calibrated=True supports only an equispaced threshold "
                f"sweep (the knob schedule targets it); got {sweep}"
            )
        t_hd = offset + achieved_sweep(len(sweep), int(sweep.max()))
        thresholds = jnp.asarray(t_hd, jnp.float32)
    else:
        thresholds = jnp.asarray(offset + sweep, jnp.int32)
    return CAMEnsembleHead(
        cam=cam,
        thresholds=thresholds,
        bias_cells=cfg.bias_cells,
    )


# ---------------------------------------------------------------------------
# Execution modes
# ---------------------------------------------------------------------------
def votes_faithful(
    head: CAMEnsembleHead,
    x_pm1: jax.Array,
    *,
    noise: NoiseModel = NOISELESS,
    key: Optional[jax.Array] = None,
    params: Optional[AnalogParams] = None,
    physics: Optional[SearchPhysics] = None,
) -> jax.Array:
    """The silicon flow: one search per threshold, per-pass PVT noise.

    x_pm1: [..., n_in] +-1 activations. Returns int32 votes [..., classes].

    The effective per-pass thresholds come from the unified sampler
    (`SearchPhysics.sample`) — ALL NoiseModel terms apply (sigma_hd per
    row; sigma_vref / sigma_tjitter pass-global through the Table-I knob
    schedule; temp_drift_hd systematic).  Pass `physics` to reuse a
    prebuilt bundle; otherwise one is built from (head, noise, params).
    """
    q = query_with_bias(x_pm1, head.bias_cells)
    hd = head.cam.search_hd(q).astype(jnp.float32)  # [..., C] (analog ML)
    phys = physics or SearchPhysics.for_head(head, noise, params)
    t_eff = phys.sample(key, batch_shape=hd.shape[:-1], n_rows=hd.shape[-1])
    votes = jnp.zeros(hd.shape, jnp.int32)
    for t in range(phys.n_passes):  # one search per pass, as in silicon
        votes = votes + (hd <= t_eff[t]).astype(jnp.int32)
    return votes


def votes_fused(head: CAMEnsembleHead, x_pm1: jax.Array) -> jax.Array:
    """Beyond-paper fused sweep: HD once, all thresholds in-register.

    The noiseless limit (the TPU compare is exact); bit-identical to
    votes_faithful(..., noise=NOISELESS).  For the silicon-conditioned
    twin with the same HD-once amortization see `votes_fused_noisy`.
    """
    q = query_with_bias(x_pm1, head.bias_cells)
    hd = head.cam.search_hd(q)  # [..., C]
    # votes_j = #{t : hd_j <= T_t}; thresholds sorted ascending ->
    # votes = n_passes - searchsorted(T, hd)
    t = head.thresholds
    return (hd[..., None] <= t).sum(-1).astype(jnp.int32)


def votes_fused_noisy(
    head: CAMEnsembleHead,
    x_pm1: jax.Array,
    *,
    key: Optional[jax.Array],
    noise: NoiseModel = NOISELESS,
    params: Optional[AnalogParams] = None,
    physics: Optional[SearchPhysics] = None,
) -> jax.Array:
    """Fused sweep under PVT noise: HD once, sampled thresholds [P, ..., C].

    Identical in distribution to `votes_faithful` (same unified sampler,
    same pass/row draw structure) and bit-identical to `votes_fused` in
    the NOISELESS limit — but vectorized over passes, so Monte-Carlo
    silicon-noise evaluation runs at fused speed (the pipeline's
    Monte-Carlo specs build on the same math).
    """
    q = query_with_bias(x_pm1, head.bias_cells)
    hd = head.cam.search_hd(q).astype(jnp.float32)  # [..., C]
    phys = physics or SearchPhysics.for_head(head, noise, params)
    t_eff = phys.sample(key, batch_shape=hd.shape[:-1], n_rows=hd.shape[-1])
    return (hd[None] <= t_eff).sum(0).astype(jnp.int32)


def votes_kernel(head: CAMEnsembleHead, x_pm1: jax.Array) -> jax.Array:
    """Pallas kernel path (interpret-mode on CPU). Same semantics as fused.

    Routed through the fused end-to-end pipeline kernel (kernels/fused_mlp)
    in its degenerate head-only form — one kernel, query in VMEM, votes
    out. The standalone cam_vote kernel remains for sub-head workloads.
    """
    from repro.kernels import fused_mlp  # local: kernels are optional deps

    q = query_with_bias(x_pm1, head.bias_cells)
    return fused_mlp.fused_mlp_votes(
        q, (), (), (), head.cam.rows_packed, head.thresholds,
        bias_cells=head.bias_cells, bq=128,
        interpret=jax.default_backend() != "tpu",
    )


def predict(
    head: CAMEnsembleHead,
    x_pm1: jax.Array,
    cfg: EnsembleConfig,
    *,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Algorithm 1 final prediction: per-class majority vote -> argmax."""
    if cfg.mode == "faithful":
        v = votes_faithful(head, x_pm1, noise=cfg.noise, key=key)
    elif cfg.mode == "fused":
        v = votes_fused(head, x_pm1)
    elif cfg.mode == "kernel":
        v = votes_kernel(head, x_pm1)
    else:
        raise ValueError(f"unknown ensemble mode {cfg.mode!r}")
    return jnp.argmax(v, axis=-1)


def topk_from_votes(votes: jax.Array, k: int) -> jax.Array:
    """Top-k classes by vote count (ties broken by class index)."""
    return jnp.argsort(-votes, axis=-1)[..., :k]


def accuracy_from_cumulative(
    cum_votes: jax.Array, labels, topk=(1, 2)
) -> dict[int, dict[str, float]]:
    """{p: {topK: acc}} from per-pass cumulative votes [P, B, C].

    The shared accuracy tail of `accuracy_sweep` and the fused-pipeline
    Fig.-5 path (cumulative votes via `sweep_from_votes`).
    """
    labels = jnp.asarray(labels)[:, None]
    out = {}
    for p in range(1, cum_votes.shape[0] + 1):
        order = jnp.argsort(-cum_votes[p - 1], axis=-1)
        out[p] = {
            f"top{k}": float((order[:, :k] == labels).any(-1).mean())
            for k in topk
        }
    return out


def sweep_from_votes(votes: jax.Array, n_passes: int) -> jax.Array:
    """Per-pass cumulative vote counts recovered from the fused total.

    NOISELESS-ONLY PRECONDITION (DESIGN.md §8): the reconstruction relies
    on the per-pass match indicators being a monotone staircase in the
    (sorted) threshold schedule — true only when every pass compares the
    same exact HD.  Under PVT noise the indicators are independent
    Bernoulli draws and the staircase identity breaks; silicon-noise
    truncated sweeps must use the sampled path
    (`InferenceSpec(noise="batch", cumulative=True)`) instead.  Callers feeding a
    noisy vote total here get silently wrong per-pass counts — guard at
    the call site (see benchmarks/accuracy.py).

    With the threshold schedule sorted ascending (as `build_head` emits
    it), pass t fires on class j iff t >= n_passes - votes_j in the
    noiseless limit; so the count after the first p passes is
    clip(votes_j - (n_passes - p), 0, p).  This lets Fig.-5-style
    truncated-sweep evaluations reuse ONE fused end-to-end pipeline pass
    instead of re-searching per pass count.

    votes: [..., C] int32 fused totals -> [n_passes, ..., C] int32.
    """
    p = jnp.arange(1, n_passes + 1).reshape((-1,) + (1,) * votes.ndim)
    return jnp.clip(votes[None] - (n_passes - p), 0, p).astype(jnp.int32)


def accuracy_sweep(
    head: CAMEnsembleHead,
    hidden_pm1: jax.Array,
    labels: jax.Array,
    cfg: EnsembleConfig,
    *,
    key: Optional[jax.Array] = None,
    topk=(1, 2),
) -> dict[int, dict[str, float]]:
    """Fig. 5 reproduction: accuracy as a function of the pass count.

    Evaluates Algorithm 1 truncated to the first p thresholds, for
    p = 1..n_passes.  Returns {n_passes: {"top1": ..., "top2": ...}}.
    """
    q = query_with_bias(hidden_pm1, head.bias_cells)
    hd = head.cam.search_hd(q).astype(jnp.float32)  # [B, C]
    phys = SearchPhysics.for_head(head, cfg.noise)
    t_eff = phys.sample(key, batch_shape=hd.shape[:-1], n_rows=hd.shape[-1])
    per_pass = (hd[None] <= t_eff).astype(jnp.int32)  # [P, B, C]
    cum = jnp.cumsum(per_pass, axis=0)  # votes after p passes
    return accuracy_from_cumulative(cum, labels, topk)
