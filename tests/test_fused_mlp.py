"""The fused Pallas MLP kernel, called directly, in interpret mode.

The pipeline runs `kernels/fused_mlp.fused_mlp_votes` only on the TPU;
off it, every MLP spec runs the XLA twin.  So the kernel's CPU bar is
this file: the kernel body, run through the Pallas interpreter, must
produce the twin's votes (`pipeline._head_hd_xla` compared against the
same thresholds) and the digital oracle's (`ensemble.votes_fused`, or
`ensemble.votes_fused_noisy` under the same key) bit for bit — for the
three logical bank configurations, three hidden layers and a head-only
net, each noiseless and with a sampled `thr_samples` operand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pipeline
from repro.core import binarize, bnn, ensemble
from repro.core.cam import query_with_bias
from repro.core.device_model import SILICON
from repro.core.physics import SearchPhysics
from repro.kernels import fused_mlp

# (layer sizes, bias cells): the three nets whose head rows land on the
# macro's logical row widths (256 / 128 / 64 bits), three hidden layers,
# and a head fed straight by the input
NETS = {
    "512x256": ((300, 192, 12), 64),
    "1024x128": ((784, 64, 10), 64),
    "2048x64": ((96, 32, 5), 32),
    "three-hidden": ((120, 96, 64, 33, 7), 64),
    "head-only": ((128, 10), 64),
}
ROWS = 23  # two kernel blocks of BQ rows, the second one ragged
BQ = 16


def _random_folded(sizes, seed, bias_cells):
    """Random deployed net with fold-style parity-adjusted C_j."""
    rng = np.random.default_rng(seed)
    layers = []
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        c = bnn.parity_adjust_c(
            rng.integers(-bias_cells, bias_cells + 1, n_out), n_in, bias_cells
        )
        layers.append(bnn.FoldedLayer(
            weights_pm1=rng.choice([-1, 1], (n_out, n_in)).astype(np.int8),
            c=c,
        ))
    return layers


def _hidden_oracle(folded, x):
    """Digital oracle of the hidden layers: sign(x W^T + C) per layer."""
    h = x
    for layer in folded[:-1]:
        y = h @ jnp.asarray(layer.weights_pm1.T, jnp.float32) + jnp.asarray(
            layer.c, jnp.float32
        )
        h = jnp.where(y >= 0, 1.0, -1.0)
    return h


@pytest.mark.parametrize("noisy", [False, True], ids=["noiseless", "sampled"])
@pytest.mark.parametrize("net", sorted(NETS))
def test_fused_mlp_interpret_matches_twin_and_oracle(net, noisy):
    sizes, bias = NETS[net]
    folded = _random_folded(sizes, seed=sum(map(ord, net)), bias_cells=bias)
    head = ensemble.build_head(folded[-1], ensemble.EnsembleConfig(
        bias_cells=bias))
    hidden = folded[:-1]
    layer_ws = tuple(
        binarize.pack_bits(jnp.asarray((l.weights_pm1 > 0).astype(np.uint8)))
        for l in hidden
    )
    layer_cs = tuple(jnp.asarray(l.c, jnp.int32) for l in hidden)
    layer_n_bits = tuple(int(l.n_in) for l in hidden)
    x = jnp.asarray(
        np.random.default_rng(1).choice([-1.0, 1.0], (ROWS, sizes[0])),
        jnp.float32,
    )
    x_packed = (binarize.pack_pm1(x) if hidden
                else query_with_bias(x, head.bias_cells))

    key = jax.random.PRNGKey(5)
    if noisy:
        phys = SearchPhysics.for_head(head, SILICON)
        thr = phys.sample(key, batch_shape=(ROWS,), n_rows=head.n_classes)
    else:
        thr = None
    got = np.asarray(fused_mlp.fused_mlp_votes(
        x_packed, layer_ws, layer_cs, layer_n_bits, head.cam.rows_packed,
        head.thresholds, bias_cells=head.bias_cells, bq=BQ, interpret=True,
        thr_samples=thr,
    ))

    # the XLA twin: the same Hamming distances, the same compare
    hd = pipeline._head_hd_xla(x_packed, layer_ws, layer_cs, layer_n_bits,
                               head.cam.rows_packed, head.bias_cells)
    if noisy:
        twin = (hd.astype(jnp.float32)[None] <= thr).astype(jnp.int32).sum(0)
    else:
        twin = (hd[:, :, None] <= head.thresholds[None, None, :]).astype(
            jnp.int32).sum(-1)
    np.testing.assert_array_equal(got, np.asarray(twin))

    # the digital oracle, drawn under the same key when noisy
    h = _hidden_oracle(folded, x)
    if noisy:
        want = ensemble.votes_fused_noisy(head, h, key=key, physics=phys)
        # a real draw, not the noiseless votes
        assert (got != np.asarray(ensemble.votes_fused(head, h))).any()
    else:
        want = ensemble.votes_fused(head, h)
    np.testing.assert_array_equal(got, np.asarray(want))
