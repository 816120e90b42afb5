"""The served programs compile for a TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached: `fused_mlp_votes` is lowered with
`interpret=False` at the widths of the repo's MLP deployments, noiseless
and with the `thr_samples` operand, and each compiled program must hold
a Mosaic kernel (`tpu_custom_call`); the CNN deployments' vote programs
(the noiseless one and the batch-noise one, `kernels/fused_conv.py`),
BinaryNet's CIFAR-10 ConvNet among them at its published widths and the
benchmark's batch, must compile to int8 convolutions.  In the CIFAR-10
program each unpooled conv layer's ±1 int8 map must come out of the
conv's own output fusion, with no loop fusion that unpacks a bit-packed
predicate into it, and each pooled layer's conv must still hand its pool
the packed predicate, and no map of it is int16.  In Bi-Real-18's
program at the same batch every convolution must take int8 and give
int32 sums, and every stream map it writes must come out of a conv's
output fusion, most with their sign beside them: int16 in the stem and
stages 1-2, whose proven bound fits, int32 in stages 3-4, and no other
instruction converts, copies or relays out a stream map.  This catches
what interpret mode cannot — unsupported lowerings, unaligned slices,
scoped-VMEM overruns, programs past the chip's memory — before any chip
time is spent.

The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: a described
chip's programs cannot be read back here.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cnn import (BIREAL18_CIFAR10, CIFAR10_CONVNET,
                                     HG_CNN, MNIST_CNN, deploy_cnn)
from repro.configs.paper_mlp import HG_MLP, MNIST_MLP
from repro.core import binarize, convnet
from repro.core.convnet import CNNConfig
from repro.core.device_model import SILICON
from repro.kernels import fused_mlp
from repro.spec import InferenceSpec

DEPLOYMENTS = {
    "mnist_mlp": MNIST_MLP,
    "hg_mlp": HG_MLP,
    "mnist_cnn": MNIST_CNN,
    "hg_cnn": HG_CNN,
    "cifar10_convnet": CIFAR10_CONVNET,
}
BATCH = 256  # two kernel blocks of 128 rows
CIFAR_BATCH = 1024  # the benchmark cell's batch
PASSES = 33
# CIFAR-10 ConvNet at CIFAR_BATCH: the int8 maps of the unpooled convs
# 1, 3, 5, and the predicates of the pooled convs 2, 4, 6 bit-packed
# along W
UNPOOLED_MAPS = ("s8[1024,32,32,128]", "s8[1024,16,16,256]",
                 "s8[1024,8,8,512]")
POOLED_PREDICATES = ("u32[1024,32,128]", "u16[1024,16,256]",
                     "u8[1024,8,512]")
DEFINES = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+)\[")  # (name, dtype)
FUSION = re.compile(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])\S* fusion\(.*"
                    r"kind=(\w+), calls=%([\w.\-]+)")


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


def _conv_program(cfg, shape, noisy, batch=None):
    """The vote program of a CNN deployment and its argument shapes."""
    dep = deploy_cnn(cfg, convnet.random_folded_cnn(cfg),
                     noise=SILICON if noisy else None)
    pipe = dep.pipeline()
    spec = InferenceSpec(noise="batch") if noisy else InferenceSpec()
    prog = pipe.program(spec)
    if batch is None:
        batch = CIFAR_BATCH if cfg is CIFAR10_CONVNET else BATCH
    x = shape((batch, pipe.n_in), jnp.float32)  # raw pixels, encoded inside
    ops = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype),
                                 pipe.weight_operands)
    args = (x, shape((2,), jnp.uint32)) if noisy else (x,)
    return prog, args, ops


@pytest.mark.parametrize("noisy", [False, True], ids=["noiseless", "noisy"])
@pytest.mark.parametrize("name", sorted(DEPLOYMENTS))
def test_fused_kernel_compiles_for_v5e(name, noisy, one_chip):
    cfg = DEPLOYMENTS[name]

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    if isinstance(cfg, CNNConfig):
        prog, args, ops = _conv_program(cfg, shape, noisy)
        text = prog.lower(*args, ops=ops).compile().as_text()
        assert "convolution" in text and "s8[" in text
        return
    call, args = _mlp_call(cfg, shape)
    n_classes = args[-2].shape[0]
    thr_samples = (shape((PASSES, BATCH, n_classes), jnp.float32)
                   if noisy else None)
    compiled = jax.jit(call).lower(*args, thr_samples).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _computations(text):
    """{name: instruction lines} of a compiled HLO module's text, the
    entry computation under "ENTRY"."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif line == "}":
            name = None
        elif name:
            comps[name].append(line)
    return comps


def test_cifar_unpooled_convs_write_their_maps_from_the_conv(one_chip):
    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    prog, args, ops = _conv_program(CIFAR10_CONVNET, shape, noisy=False)
    comps = _computations(prog.lower(*args, ops=ops).compile().as_text())
    fusions = [m.groups() for m in map(FUSION.match, comps["ENTRY"]) if m]

    def conv_fusions_making(out):
        made = [(kind, calls) for o, kind, calls in fusions if o == out]
        assert made, f"no top-level fusion outputs {out}"
        return [kind == "kOutput"
                and any(" convolution(" in l for l in comps[calls])
                for kind, calls in made]

    for out in UNPOOLED_MAPS + POOLED_PREDICATES:
        assert all(conv_fusions_making(out)), out
    assert not any("s16[" in l for lines in comps.values() for l in lines)


def _fusion_outputs(entry):
    """[(kind, called computation, [(dtype, dims)])] of each top-level
    fusion, a multi-output fusion's outputs in order."""
    out = []
    for line in entry:
        head, _, rest = line.partition(" fusion(")
        m = re.search(r"kind=(\w+), calls=%([\w.\-]+)", rest)
        if not rest or not m:
            continue
        types = re.findall(r"(\w+)\[([\d,]*)\]", head.partition(" = ")[2])
        out.append((m.group(1), m.group(2), types))
    return out


def test_bireal18_convs_are_int8_and_its_stream_leaves_the_conv_fusions(
        one_chip):
    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    prog, args, ops = _conv_program(BIREAL18_CIFAR10, shape, noisy=False,
                                    batch=CIFAR_BATCH)
    comps = _computations(prog.lower(*args, ops=ops).compile().as_text())
    convs = 0
    for lines in comps.values():
        dtype = dict(m.groups() for m in map(DEFINES.match, lines) if m)
        for l in lines:
            m = re.search(r"= (\w+)\[.* convolution\(%(\S+), %(\S+)\)", l)
            if m:
                convs += 1
                assert m.group(1) == "s32", l
                assert dtype[m.group(2)] == dtype[m.group(3)] == "s8", l
    assert convs == 17 + 3 + 1  # the 3x3 convs, the downsamples, the head
    both, alone = [], []
    for kind, calls, types in _fusion_outputs(comps["ENTRY"]):
        maps = [(t, dims) for t, dims in types
                if t in ("s16", "s32") and dims.count(",") == 3]
        if not maps:
            continue
        # no loop fusion adds, converts, copies or relays out a stream map
        assert kind == "kOutput" and any(
            " convolution(" in l for l in comps[calls]), (kind, types)
        if ("s8", maps[0][1]) in types:
            both.append(maps[0])  # the stream and its sign, from the conv
        else:
            alone.append(maps[0])
    # all but four convs write the stream and its int8 sign together, as
    # int16 in the stem and stages 1-2 (bounds of at most 10,280 and
    # 16,584) and int32 in stages 3-4 (over 32,767); the three stride-2 convs write
    # their int32 sums for the downsample's fusion to add, and the last
    # conv its int32 stream for the global pool
    assert sorted(both) == sorted(
        [("s16", "1024,32,32,64")] * 5 + [("s16", "1024,16,16,128")] * 4
        + [("s32", "1024,8,8,256")] * 4 + [("s32", "1024,4,4,512")] * 3)
    assert sorted(alone) == sorted(
        ("s32", dims) for dims in ["1024,16,16,128", "1024,8,8,256",
                                   "1024,4,4,512", "1024,4,4,512"])
    # outside the fusions nothing converts, copies or relays out a map
    for l in comps["ENTRY"]:
        if re.search(r"s(16|32)\[1024,\d+,\d+,\d+\]", l):
            assert " fusion(" in l or " get-tuple-element(" in l, l
