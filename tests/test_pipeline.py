"""Fused packed-domain pipeline: bit-exactness vs the digital oracle.

The correctness bar for kernels/fused_mlp.py and repro/pipeline.py: the
fused end-to-end flow must be bit-identical to `bnn.folded_forward_exact`
(hidden layers) + `ensemble.votes_fused` (head), across the three logical
bank configurations of the silicon macro, for both input forms: a host
NumPy batch (packed on the host for an MLP with hidden layers) and a
committed `jax.Array` (packed by the `picbnn_pack` program on the
device).  Off the TPU the pipeline runs the single-program XLA twin; the
Pallas kernel's own bit-exactness is tests/test_fused_mlp.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pipeline
from repro.core import binarize, bnn, ensemble
from repro.core.cam import pick_bank_config
from repro.spec import InferenceSpec

# Net shapes whose head rows (n_hidden + 64 bias cells) land on each of
# the macro's three logical row widths: 256 / 128 / 64 bits.
BANK_NETS = {
    "512x256": (300, 192, 12),  # head row 192 + 64 = 256 bits
    "1024x128": (784, 64, 10),  # head row 64 + 64 = 128 bits
    "2048x64": (96, 32, 5),  # head row 32 + 32 = 64 bits (32 bias cells)
}
BANK_BIAS = {"512x256": 64, "1024x128": 64, "2048x64": 32}


def _random_folded(sizes, seed, bias_cells):
    """Random deployed net with fold-style parity-adjusted C_j."""
    rng = np.random.default_rng(seed)
    layers = []
    for i in range(len(sizes) - 1):
        n_in, n_out = sizes[i], sizes[i + 1]
        c = bnn.parity_adjust_c(
            rng.integers(-bias_cells, bias_cells + 1, n_out), n_in, bias_cells
        )
        layers.append(bnn.FoldedLayer(
            weights_pm1=rng.choice([-1, 1], (n_out, n_in)).astype(np.int8),
            c=c,
        ))
    return layers


def _oracle_votes(folded, head, x):
    """Digital oracle: folded_forward_exact hidden flow + votes_fused."""
    h = x
    for layer in folded[:-1]:
        y = h @ jnp.asarray(layer.weights_pm1.T, jnp.float32) + jnp.asarray(
            layer.c, jnp.float32
        )
        h = jnp.where(y >= 0, 1.0, -1.0)
    return ensemble.votes_fused(head, h)


#: how a caller hands a batch to `run`: a host NumPy array, or a
#: `jax.Array` already committed to the device
INPUTS = ["host", "device"]


def _as_input(x, form):
    x = np.asarray(x, np.float32)
    if form == "host":
        return x
    return jax.device_put(x, jax.devices()[0])


def _votes(pipe, x, key=None):
    """The noise-off votes, or one batch-level draw under `key`."""
    if key is None:
        return np.asarray(pipe.run(x, InferenceSpec()))
    return np.asarray(pipe.run(x, InferenceSpec(noise="batch"), key=key))


@pytest.mark.parametrize("bank", sorted(BANK_NETS))
@pytest.mark.parametrize("form", INPUTS)
def test_pipeline_bit_exact_vs_oracle(bank, form):
    sizes = BANK_NETS[bank]
    bias = BANK_BIAS[bank]
    rows, width = (int(s) for s in bank.split("x"))
    # the head really does land on this logical configuration
    assert pick_bank_config(sizes[1] + bias).width == width

    folded = _random_folded(sizes, seed=sum(map(ord, bank)), bias_cells=bias)
    ecfg = ensemble.EnsembleConfig(bias_cells=bias)
    pipe = pipeline.compile_pipeline(folded, ecfg)
    x = np.random.default_rng(1).choice([-1.0, 1.0], (23, sizes[0]))
    want = np.asarray(_oracle_votes(folded, pipe.head, jnp.asarray(x,
                                                                  jnp.float32)))
    got = _votes(pipe, _as_input(x, form))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("form", INPUTS)
def test_pipeline_three_hidden_layers(form):
    folded = _random_folded((120, 96, 64, 33, 7), seed=5, bias_cells=64)
    ecfg = ensemble.EnsembleConfig()
    pipe = pipeline.compile_pipeline(folded, ecfg)
    x = np.random.default_rng(2).choice([-1.0, 1.0], (11, 120))
    want = np.asarray(_oracle_votes(folded, pipe.head,
                                    jnp.asarray(x, jnp.float32)))
    np.testing.assert_array_equal(_votes(pipe, _as_input(x, form)), want)


@pytest.mark.parametrize("form", INPUTS)
def test_pipeline_head_only(form):
    """Degenerate pipeline (no hidden layers) == votes_fused on the head:
    a host batch is staged, a device batch passes through, and both are
    packed with the bias cells on the device."""
    folded = _random_folded((128, 10), seed=9, bias_cells=64)
    ecfg = ensemble.EnsembleConfig()
    pipe = pipeline.compile_pipeline(folded, ecfg)
    x = np.random.default_rng(3).choice([-1.0, 1.0], (9, 128))
    want = np.asarray(ensemble.votes_fused(pipe.head,
                                           jnp.asarray(x, jnp.float32)))
    np.testing.assert_array_equal(_votes(pipe, _as_input(x, form)), want)


def test_pipeline_matches_votes_faithful_noiseless():
    """Fused pipeline == the 33-sequential-search silicon flow (noiseless)."""
    folded = _random_folded((784, 128, 10), seed=11, bias_cells=64)
    ecfg = ensemble.EnsembleConfig()
    pipe = pipeline.compile_pipeline(folded, ecfg)
    x = np.random.default_rng(4).choice([-1.0, 1.0], (17, 784))
    x = jnp.asarray(x, jnp.float32)
    # hidden flow via the digital oracle, head via the faithful sweep
    h = x
    for layer in folded[:-1]:
        y = h @ jnp.asarray(layer.weights_pm1.T, jnp.float32) + jnp.asarray(
            layer.c, jnp.float32
        )
        h = jnp.where(y >= 0, 1.0, -1.0)
    want = np.asarray(ensemble.votes_faithful(pipe.head, h))
    np.testing.assert_array_equal(_votes(pipe, x), want)


def test_pipeline_batch_bucketing():
    """Ragged batch sizes pad to power-of-two buckets; results unaffected."""
    folded = _random_folded((100, 48, 6), seed=13, bias_cells=64)
    pipe = pipeline.compile_pipeline(
        folded, ensemble.EnsembleConfig(), min_bucket=32
    )
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.choice([-1.0, 1.0], (70, 100)), jnp.float32)
    full = _votes(pipe, x)
    for b in (1, 31, 32, 33, 70):
        np.testing.assert_array_equal(_votes(pipe, x[:b]), full[:b])
    assert pipeline.next_bucket(33, 32) == 64
    assert pipeline.next_bucket(32, 32) == 32
    assert pipeline.next_bucket(1, 32) == 32


def test_next_bucket_max_bucket_boundaries():
    """Boundary behavior at the serving cap: n == max_bucket passes, one
    more fails loudly, and a non-power-of-two cap rejects any n whose
    bucket overshoots it (even with n < max_bucket)."""
    assert pipeline.next_bucket(64, 64, max_bucket=64) == 64
    assert pipeline.next_bucket(1, 64, max_bucket=64) == 64
    assert pipeline.next_bucket(128, 64, max_bucket=128) == 128
    with pytest.raises(ValueError, match="max_bucket"):
        pipeline.next_bucket(65, 64, max_bucket=64)
    with pytest.raises(ValueError, match="max_bucket"):
        pipeline.next_bucket(129, 64, max_bucket=128)
    # a non-power-of-two cap: 70 buckets to 128 > 100 -> reject
    with pytest.raises(ValueError, match="max_bucket"):
        pipeline.next_bucket(70, 64, max_bucket=100)
    assert pipeline.next_bucket(60, 64, max_bucket=100) == 64
    # empty batches still rejected regardless of cap
    with pytest.raises(ValueError, match=">= 1"):
        pipeline.next_bucket(0, 64, max_bucket=64)


def test_pack_unpack_roundtrip_multidim():
    """pack_bits/unpack_bits round-trip with multi-dim leading axes, and
    the dot-product fast path matches the shift-broadcast reference."""
    rng = np.random.default_rng(7)
    for shape in [(3, 5, 77), (2, 2, 2, 33), (4, 31), (1, 1, 1, 256), (6,)]:
        bits = rng.integers(0, 2, shape).astype(np.uint8)
        packed = binarize.pack_bits(jnp.asarray(bits))
        assert packed.shape == (
            *shape[:-1], binarize.packed_width(shape[-1])
        )
        np.testing.assert_array_equal(
            np.asarray(packed),
            np.asarray(binarize.pack_bits_reference(jnp.asarray(bits))),
        )
        np.testing.assert_array_equal(
            np.asarray(binarize.unpack_bits(packed, shape[-1])), bits
        )


def test_fold_emits_dead_zone_free_constants():
    """fold's C_j has parity opposite n_in: sign(y + C) never sees zero."""
    cfg = bnn.MLPConfig(layer_sizes=(784, 64, 10), bias_cells=64)
    params = bnn.init_params(jax.random.PRNGKey(0), cfg)
    # perturb BN so C_j is nontrivial
    for i, layer in enumerate(params["layers"]):
        k = jax.random.PRNGKey(i + 1)
        layer["beta"] = jax.random.normal(k, layer["beta"].shape) * 3
        layer["mean"] = jax.random.normal(k, layer["mean"].shape) * 5
    folded = bnn.fold(params, cfg)
    for layer in folded:
        assert ((layer.c + layer.n_in) % 2 == 1).all(), layer.c
        assert (np.abs(layer.c) <= cfg.bias_cells).all()


@pytest.mark.parametrize("bank", sorted(BANK_NETS))
@pytest.mark.parametrize("form", INPUTS)
def test_noisy_pipeline_noiseless_limit_bit_exact(bank, form):
    """sigma -> 0 limit: every silicon spec (batch and per-request draws,
    Monte-Carlo with and without the fused sum, cumulative) equals the
    noiseless oracle bit-for-bit on all three bank configurations."""
    from repro.core.device_model import NOISELESS

    sizes, bias = BANK_NETS[bank], BANK_BIAS[bank]
    folded = _random_folded(sizes, seed=sum(map(ord, bank)), bias_cells=bias)
    ecfg = ensemble.EnsembleConfig(bias_cells=bias)
    pipe = pipeline.compile_pipeline(folded, ecfg, noise=NOISELESS)
    x_np = np.random.default_rng(8).choice([-1.0, 1.0], (19, sizes[0]))
    x = _as_input(x_np, form)
    key = jax.random.PRNGKey(42)
    want = np.asarray(_oracle_votes(folded, pipe.head,
                                    jnp.asarray(x_np, jnp.float32)))
    np.testing.assert_array_equal(_votes(pipe, x, key), want)
    keys = jax.random.split(key, x_np.shape[0])
    np.testing.assert_array_equal(
        np.asarray(pipe.run(x, InferenceSpec(noise="per_request"),
                            keys=keys)), want)
    mc = np.asarray(pipe.run(x, InferenceSpec(noise="batch", mc_samples=3),
                             key=key))
    np.testing.assert_array_equal(mc, np.broadcast_to(want, mc.shape))
    np.testing.assert_array_equal(
        np.asarray(pipe.run(x, InferenceSpec(noise="per_request",
                                             mc_samples=3, reduction="sum"),
                            keys=keys)), want * 3)
    cum = np.asarray(pipe.run(x, InferenceSpec(noise="batch",
                                               cumulative=True), key=key))
    np.testing.assert_array_equal(cum[-1], want)
    np.testing.assert_array_equal(
        cum,
        np.asarray(ensemble.sweep_from_votes(jnp.asarray(want),
                                             cum.shape[0])),
    )


@pytest.mark.parametrize("bank", sorted(BANK_NETS))
@pytest.mark.parametrize("form", INPUTS)
def test_noisy_pipeline_impls_agree_under_silicon(bank, form):
    """Same key => the batch-draw votes equal the last pass of the
    cumulative batch-draw spec (on the TPU: the Pallas kernel against
    the XLA twin, since the thresholds are sampled outside the kernel),
    they equal ensemble's fused noisy twin draw for draw, and a silicon
    draw actually differs from the noiseless votes."""
    from repro.core.device_model import SILICON

    sizes, bias = BANK_NETS[bank], BANK_BIAS[bank]
    folded = _random_folded(sizes, seed=23, bias_cells=bias)
    ecfg = ensemble.EnsembleConfig(bias_cells=bias)
    pipe = pipeline.compile_pipeline(folded, ecfg, noise=SILICON)
    # batch == bucket so the in-program sample shape equals the logical
    # batch (the draw-for-draw comparison below needs identical shapes)
    x_np = np.random.default_rng(9).choice([-1.0, 1.0], (64, sizes[0]))
    x = _as_input(x_np, form)
    key = jax.random.PRNGKey(5)
    got = _votes(pipe, x, key)
    # silicon noise perturbs (vs noiseless) ...
    assert (got != _votes(pipe, x)).any()
    # ... and the cumulative twin's last pass is the same draw
    cum = pipe.run(x, InferenceSpec(noise="batch", cumulative=True), key=key)
    np.testing.assert_array_equal(got, np.asarray(cum)[-1])
    # and the noisy path is draw-for-draw equal to ensemble's fused twin
    h = jnp.asarray(x_np, jnp.float32)
    for layer in folded[:-1]:
        y = h @ jnp.asarray(layer.weights_pm1.T, jnp.float32) + jnp.asarray(
            layer.c, jnp.float32
        )
        h = jnp.where(y >= 0, 1.0, -1.0)
    want = np.asarray(ensemble.votes_fused_noisy(
        head=pipe.head, x_pm1=h, key=key, physics=pipe.physics))
    np.testing.assert_array_equal(got, want)


def test_pipeline_without_noise_rejects_key():
    folded = _random_folded((128, 10), seed=31, bias_cells=64)
    pipe = pipeline.compile_pipeline(folded, ensemble.EnsembleConfig())
    x = jnp.asarray(
        np.random.default_rng(11).choice([-1.0, 1.0], (4, 128)), jnp.float32
    )
    with pytest.raises(ValueError, match="noise="):
        pipe.run(x, InferenceSpec(noise="batch"), key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="noise="):
        pipe.run(x, InferenceSpec(noise="batch", mc_samples=2),
                 key=jax.random.PRNGKey(0))


def test_sweep_from_votes_matches_accuracy_sweep_cumsum():
    """The truncated-sweep recovery identity behind the fused Fig. 5 path."""
    folded = _random_folded((128, 10), seed=21, bias_cells=64)
    ecfg = ensemble.EnsembleConfig()
    head = ensemble.build_head(folded[-1], ecfg)
    x = binarize.random_pm1(jax.random.PRNGKey(2), (12, 128))
    from repro.core.cam import query_with_bias

    hd = head.cam.search_hd(query_with_bias(x, head.bias_cells))
    per_pass = np.asarray(
        (hd[None] <= head.thresholds[:, None, None]).astype(jnp.int32)
    )
    want = np.cumsum(per_pass, axis=0)
    votes = ensemble.votes_fused(head, x)
    got = np.asarray(ensemble.sweep_from_votes(votes, ecfg.n_passes))
    np.testing.assert_array_equal(got, want)
