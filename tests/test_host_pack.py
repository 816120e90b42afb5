"""Host packing of ±1 batches (`binarize.pack_pm1_host`) and where the
pipeline uses it: bit-equal to the device pack for every input dtype and
width, the same votes as the device path, and staging of packed words
only for host inputs of MLPs with hidden layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, pipeline
from repro.configs.paper_cnn import build_cnn_pipeline
from repro.core import binarize, bnn, convnet, ensemble
from repro.core.binarize import InputEncoding
from repro.core.convnet import CNNConfig, ConvSpec
from repro.deploy import deploy
from repro.spec import InferenceSpec

# every value class the compare has to agree on, cast per dtype below
EDGES = np.array([-1.0, 1.0, 0.0, -0.0, np.nan, np.inf, -np.inf, 1e-50,
                  -1e-50, 3.5])
INT_EDGES = {np.int8: [-1, 1, 0, 127, -128, 5],
             # 2**32 wraps to 0 and 2**31 to -2**31 once staged as int32
             np.int64: [-1, 1, 0, 2**32, 2**31, -(2**32) + 1, 7]}


def _values(rng, shape, dtype):
    if dtype is bool:
        return rng.integers(0, 2, shape).astype(bool)
    if dtype in INT_EDGES:
        return rng.choice(np.array(INT_EDGES[dtype], np.int64),
                          shape).astype(dtype)
    return rng.choice(EDGES, shape).astype(dtype)


def _device_pack(x):
    return np.asarray(jax.jit(binarize.pack_pm1)(jax.device_put(x)))


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int8,
                                   np.int64, bool])
@pytest.mark.parametrize("n", [1, 31, 33, 784, 4096])
def test_host_pack_is_bit_equal_to_the_device_pack(n, dtype, monkeypatch):
    # small chunks, so every width packs below, at and above one chunk,
    # and on several threads
    monkeypatch.setattr(binarize, "_HOST_CHUNK_BYTES", 4096)
    rng = np.random.default_rng(n)
    staged = jax.dtypes.canonicalize_dtype(np.dtype(dtype)).itemsize
    least = max(1, 4096 // (n * staged))
    for b in sorted({max(1, least - 1), least, least + 1, 5 * least + 3}):
        x = _values(rng, (b, n), dtype)
        got = binarize.pack_pm1_host(x)
        assert got.dtype == np.uint32
        assert got.shape == (b, binarize.packed_width(n))
        np.testing.assert_array_equal(got, _device_pack(x))


def test_host_pack_of_a_full_offline_batch():
    # the benchmark's batch at the real chunk size: a chunk per thread
    x = _values(np.random.default_rng(7), (2048, 4096), np.float32)
    np.testing.assert_array_equal(binarize.pack_pm1_host(x),
                                  _device_pack(x))


def test_host_pack_of_a_strided_slice():
    rng = np.random.default_rng(0)
    x = _values(rng, (2 * 200 + 1, 2 * 784), np.float32)[::2, 1::2]
    assert not x.flags.c_contiguous
    np.testing.assert_array_equal(binarize.pack_pm1_host(x),
                                  _device_pack(np.ascontiguousarray(x)))


@pytest.fixture(scope="module", params=[(784, 128, 10), (4096, 128, 20)],
                ids=["mnist", "hg"])
def mlp(request):
    cfg = bnn.MLPConfig(layer_sizes=request.param)
    return deploy(bnn.random_folded(cfg, seed=1), config=cfg)


@pytest.mark.parametrize("b", [100, 128], ids=["padded", "unpadded"])
def test_host_and_device_inputs_vote_alike(mlp, b):
    rng = np.random.default_rng(b)
    x = rng.choice(np.array([-1.0, 1.0], np.float32),
                   (b, mlp.pipeline().n_in))
    for spec in (InferenceSpec(), InferenceSpec(reduction="argmax")):
        host = np.asarray(mlp.run(x, spec))
        dev = np.asarray(mlp.run(jnp.asarray(x), spec))
        assert host.shape[0] == b
        np.testing.assert_array_equal(host, dev)


def _delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _cnn():
    cfg = CNNConfig(side=12, encoding=InputEncoding("thermometer", 3),
                    conv=(ConvSpec(3, 24, 2), ConvSpec(3, 20, 1)),
                    hidden=(48,), n_classes=7)
    return build_cnn_pipeline(cfg, convnet.random_folded_cnn(cfg, seed=3))


def _head_only():
    cfg = bnn.MLPConfig(layer_sizes=(96, 5), bias_cells=32)
    return pipeline.compile_pipeline(
        bnn.random_folded(cfg, seed=2, cmax=10),
        ensemble.EnsembleConfig(bias_cells=32))


def test_host_mlp_input_stages_packed_words(mlp):
    pipe = mlp.pipeline()
    x = np.ones((13, pipe.n_in), np.float32)
    before = obs.counters()
    jax.block_until_ready(pipe.run(x, InferenceSpec()))
    got = _delta(before, obs.counters())
    assert got["stage.bytes"] == 13 * 4 * binarize.packed_width(pipe.n_in)
    assert got["stage.rows"] == 13
    assert (got["pack.host_calls"], got["pack.host_rows"]) == (1, 13)
    assert got["pack.host_ns"] > 0


@pytest.mark.parametrize("case", ["device_array", "conv", "head_only"])
def test_other_inputs_stage_raw_rows_and_skip_the_host_pack(case):
    if case == "device_array":
        cfg = bnn.MLPConfig()
        pipe = deploy(bnn.random_folded(cfg, seed=1),
                      config=cfg).pipeline()
    else:
        pipe = _cnn() if case == "conv" else _head_only()
    x = np.full((13, pipe.n_in), 0.5 if case == "conv" else 1.0, np.float32)
    before = obs.counters()
    # a device array is staged by its owner, as the server stages batches
    xin = obs.stage(x) if case == "device_array" else x
    jax.block_until_ready(pipe.run(xin, InferenceSpec()))
    got = _delta(before, obs.counters())
    assert got["stage.bytes"] == 13 * 4 * pipe.n_in
    assert got["stage.rows"] == 13
    assert not any(k.startswith("pack.host_") for k in got)
