"""The served Pallas kernels compile for a TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached: `fused_mlp_votes` and `fused_conv_votes` are
lowered with `interpret=False` at the widths of the repo's four
deployments, noiseless and with the `thr_samples` operand, and each
compiled program must hold a Mosaic kernel (`tpu_custom_call`).  This
catches what interpret mode cannot — unsupported lowerings, unaligned
slices, scoped-VMEM overruns — before any chip time is spent.

The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: a described
chip's programs cannot be read back here.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cnn import HG_CNN, MNIST_CNN
from repro.configs.paper_mlp import HG_MLP, MNIST_MLP
from repro.core import binarize, convnet
from repro.core.convnet import CNNConfig
from repro.kernels import fused_conv, fused_mlp

DEPLOYMENTS = {
    "mnist_mlp": MNIST_MLP,
    "hg_mlp": HG_MLP,
    "mnist_cnn": MNIST_CNN,
    "hg_cnn": HG_CNN,
}
BATCH = 256  # two kernel blocks of 128 rows
PASSES = 33


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _mlp_call(cfg, shape):
    sizes = cfg.layer_sizes
    hidden = list(zip(sizes[:-2], sizes[1:-1]))
    args = (
        shape((BATCH, binarize.packed_width(sizes[0])), jnp.uint32),
        tuple(shape((n_out, binarize.packed_width(n_in)), jnp.uint32)
              for n_in, n_out in hidden),
        tuple(shape((n_out,), jnp.int32) for _, n_out in hidden),
        shape((sizes[-1], binarize.packed_width(sizes[-2] + cfg.bias_cells)),
              jnp.uint32),
        shape((PASSES,), jnp.int32),
    )
    n_bits = tuple(n_in for n_in, _ in hidden)

    def call(x, ws, cs, head, thr, thr_samples):
        return fused_mlp.fused_mlp_votes(
            x, ws, cs, n_bits, head, thr, bias_cells=cfg.bias_cells,
            thr_samples=thr_samples,
        )

    return call, args


def _conv_call(cfg, shape):
    folded = convnet.random_folded_cnn(cfg)
    conv = folded[:len(cfg.conv)]
    metas = fused_conv.conv_metas_for(conv, cfg.side)
    m0, mf = metas[0], metas[-1]
    fc = cfg.fc_sizes
    hidden = list(zip(fc[:-2], fc[1:-1]))
    args = (
        shape((BATCH, cfg.side, cfg.side, m0.cw_in), jnp.uint32),
        tuple(shape((m.c_out, m.k * m.k * m.cw_in), jnp.uint32)
              for m in metas),
        tuple(shape((m.c_out,), jnp.int32) for m in metas),
        tuple(shape((n_out, mf.out_side ** 2 * mf.cw_out if i == 0
                     else binarize.packed_width(n_in)), jnp.uint32)
              for i, (n_in, n_out) in enumerate(hidden)),
        tuple(shape((n_out,), jnp.int32) for _, n_out in hidden),
        shape((cfg.n_classes,
               binarize.packed_width(fc[-2] + cfg.bias_cells)), jnp.uint32),
        shape((PASSES,), jnp.int32),
    )
    n_bits = tuple(n_in for n_in, _ in hidden)

    def call(x, cws, ccs, ws, cs, head, thr, thr_samples):
        return fused_conv.fused_conv_votes(
            x, cws, ccs, metas, ws, cs, n_bits, head, thr,
            bias_cells=cfg.bias_cells, head_direct=not hidden,
            thr_samples=thr_samples,
        )

    return call, args


@pytest.mark.parametrize("noisy", [False, True], ids=["noiseless", "noisy"])
@pytest.mark.parametrize("name", sorted(DEPLOYMENTS))
def test_fused_kernel_compiles_for_v5e(name, noisy, one_chip):
    cfg = DEPLOYMENTS[name]

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    build = _conv_call if isinstance(cfg, CNNConfig) else _mlp_call
    call, args = build(cfg, shape)
    n_classes = args[-2].shape[0]
    thr_samples = (shape((PASSES, BATCH, n_classes), jnp.float32)
                   if noisy else None)
    compiled = jax.jit(call).lower(*args, thr_samples).compile()
    assert "tpu_custom_call" in compiled.as_text()
