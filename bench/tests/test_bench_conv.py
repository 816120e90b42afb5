"""The binarynet_cifar10 configuration: its counts at published widths,
and, at a reduced size on the host, its reference against the program,
the control against the reference, whole runs of its cell and its
per-layer readers."""

import copy
import json
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from bench import check, model as M, peaks, run as R  # noqa: E402
from bench.configs import binarynet_cifar10 as C  # noqa: E402

SEED = 2 ** 31 + 4242
CELL = "binarynet_cifar10.offline_b1024"
# BinaryNet's pattern cut to 8x8x3, 32/64 channels, one hidden FC layer
SMALL = copy.deepcopy(C.CONFIG)
SMALL.update(side=8, hidden=[48], conv=[
    {"k": 3, "c_out": 32, "stride": 1, "padding": "same", "pool": 1},
    {"k": 3, "c_out": 32, "stride": 1, "padding": "same", "pool": 2},
    {"k": 3, "c_out": 64, "stride": 1, "padding": "valid", "pool": 2},
])


def test_counts_at_published_widths():
    assert C.macs_per_row() == 641_739_392  # the head's bias cells in
    assert C.ops_per_row() == 2 * 641_739_392
    assert C.weight_bits() == 14_046_208  # without them
    assert C.in_bits_per_row() == 32 * 32 * 3 * 8
    convs, fc = C._layers(C.CONFIG)
    conv_macs = sum(s * s * o * k * k * i for k, i, o, s in convs)
    assert conv_macs / C.macs_per_row() == pytest.approx(0.985, abs=5e-4)
    assert fc == (8192, 1024, 1024, 10)


def test_kernel_bytes_are_input_votes_and_weights_per_call():
    m = M.Model(name="m", deployment=None, n_in=3072, n_classes=10,
                kernel="picbnn_votes_off", ops_per_row=C.ops_per_row(),
                weight_bits=C.weight_bits(),
                in_bits_per_row=C.in_bits_per_row(), rows=None, hd=None,
                thresholds=None)
    assert m.kernel_bytes(1024, 1) == 1024 * (3072 + 40) + 14_046_208 / 8


@pytest.fixture(scope="module")
def small():
    return C.build(SEED, SMALL)


def _rows(model, n):
    return model.rows(np.random.default_rng(M.seeds(SEED)["traffic"]), n)


def test_reference_agrees_with_deployment_run(small):
    from repro.spec import InferenceSpec

    x = _rows(small, 40)
    assert x.min() >= 0 and x.max() <= 1
    assert np.allclose(x * 255, np.round(x * 255))
    votes = np.asarray(small.deployment.run(x, InferenceSpec()))
    want = check.reference_votes(small, x)
    assert votes.shape == want.shape == (40, 10)
    assert check.wrong_rows(votes, want) == 0
    assert len(np.unique(votes)) > 3


def test_control_in_lower_precision_is_caught(small):
    x = _rows(small, 32)
    want = check.reference_votes(small, x)
    ctl = check.reference_votes(small, x, "float8_e4m3fn")
    assert check.wrong_rows(ctl, want) > check.LIMITS["wrong_rows"]


def test_weights_reach_the_program_in_deployment_form(small):
    """Half the channels of every conv layer carry s = -1: their rows
    are negated for the program and their pools AND."""
    convs = small.deployment.conv_layers
    for layer, spec in zip(convs, SMALL["conv"]):
        assert (layer.padding, layer.pool) == (spec["padding"], spec["pool"])
        if layer.pool > 1:
            assert (layer.pool_sign == -1).sum() == layer.c_out // 2


def _small_module(monkeypatch):
    load = R.load_module

    def patched(path):
        mod = load(path)
        if path.stem != "binarynet_cifar10":
            return mod
        return types.SimpleNamespace(
            build=lambda seed: mod.build(seed, SMALL))

    monkeypatch.setattr(R, "load_module", patched)


@pytest.mark.parametrize("altered", [False, True], ids=["sound", "altered"])
def test_whole_run_of_the_cell_on_the_host(altered, monkeypatch):
    _small_module(monkeypatch)
    if altered:
        from repro import pipeline

        run_packed = pipeline.CompiledPipeline.run_packed

        def alter(self, *a, **kw):
            return run_packed(self, *a, **kw).at[0, 0].add(1)

        monkeypatch.setattr(pipeline.CompiledPipeline, "run_packed", alter)
    res, _ = R.run(CELL, SEED, 0.4, False, devices=jax.devices()[:1],
                   mix_override={"batch": 64})
    assert res["correct"] is (not altered)
    assert set(res["metrics"]) == {"offline_inf_per_s", "setup_s"}
    json.dumps(res)


def _metric(name):
    return R.load_module(R.BENCH / "metrics" / f"{name}.py")


def test_roofline_reads_the_vote_program_ops(small):
    kind = "TPU v5 lite"
    ctx = types.SimpleNamespace(
        model=small, mix={"batch": 64}, device_kind=kind,
        window={"answered": 640},
        trace={"top_ops": [["jit_picbnn_votes_off/fusion", 2e-3],
                           ["jit_picbnn_votes_off/convolution", 1e-3],
                           ["jit_picbnn_pack/fusion", 5.0]]})
    least, _ = peaks.least_time_s(small.kernel_ops(640),
                                  small.kernel_bytes(640, 10), kind)
    read = _metric("picbnn_votes_off_roofline.cifar10").read
    assert read(ctx) == pytest.approx(100.0 * least / 3e-3)
    ctx.trace = {"top_ops": [["jit_picbnn_pack/fusion", 1.0]]}
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_weight_bytes_per_row_reads_the_program_counters(small):
    from repro import obs
    from repro.spec import InferenceSpec

    read = _metric("kernel.weight_bytes_per_inf.cifar10").read
    assert read(types.SimpleNamespace(trace=None)) is None
    before = obs.counters()
    small.deployment.run(_rows(small, 10), InferenceSpec())
    after = obs.counters()
    pipe = small.deployment.pipeline()
    assert after["kernel.weight_bytes"] - before.get(
        "kernel.weight_bytes", 0) == pipe.weight_bytes
    assert after["kernel.rows"] - before.get("kernel.rows", 0) == 10
    got = read(types.SimpleNamespace(trace={}))
    assert got == after["kernel.weight_bytes"] / after["kernel.rows"]
    leaves = jax.tree_util.tree_leaves(pipe.weight_operands)
    assert pipe.weight_bytes == sum(a.nbytes for a in leaves)
