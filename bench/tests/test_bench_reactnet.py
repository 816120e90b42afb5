"""The reactnet_a_imagenet configuration: its counts at published widths,
the control against the reference there, and, at a reduced size on the
host, its reference against the program (`Deployment.run`, the server,
a saved and loaded deployment, and the program's own oracle), whole
runs of its cell and its per-layer readers."""

import copy
import json
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from bench import check, model as M, peaks, run as R  # noqa: E402
from bench.configs import reactnet_a_imagenet as C  # noqa: E402

SEEDS = (2 ** 31 + 4243, 2 ** 33 + 7)
CELL = "reactnet_a_imagenet.offline_b256"
# ReActNet-A's pattern cut to 16x16x3 and 8/16/16/32 channels: a
# stride-2 stem, a doubling block, an identity block and a stride-2
# doubling block, whose 4x4 map the global pool sums
SMALL = copy.deepcopy(C.CONFIG)
SMALL.update(side=16, stem=8, blocks=[[16, 1], [16, 1], [32, 2]],
             global_pool=4, n_classes=10)


def test_counts_at_published_widths():
    assert C.macs_per_row() == 4_903_600_128 + 1_000 * 1_088
    assert C.ops_per_row() == 9_809_376_256
    assert C.weight_bits() == 29_284_096  # without the bias cells
    assert C.stream_elements_per_row() == 5_042_688
    assert C.in_bits_per_row() == 32 * 150_528  # float32 pixels
    convs = C._convs(C.CONFIG)
    assert len(convs) == 27
    assert [sc for *_, sc, _ in convs].count("avg") == 4
    assert [sc for *_, sc, _ in convs].count("dup") == 5
    assert [s for *_, s in convs[2::2]] == [112] + [56] * 2 + [28] * 2 \
        + [14] * 6 + [7] * 2
    # the stem through block 5's 1x1 hold most of the stream
    assert sum(s * s * co for _, co, *_, s in convs[:11]) == 3_713_024


def test_the_program_config_is_the_file():
    from repro.configs.paper_cnn import REACTNET_A_IMAGENET

    assert C.cnn_config() == REACTNET_A_IMAGENET
    assert C.CONFIG["program_config"].endswith("REACTNET_A_IMAGENET")
    assert C.CONFIG["reduced"] == []


def _ref_votes(model, x, dtype=None):
    """The reference's votes of rows `x` as they are, unpadded (the
    check pads every call to 2,048 rows)."""
    from bench.reference import bnn

    t = bnn.rounder(dtype)(jax.numpy.asarray(model.thresholds))
    return np.asarray(bnn.votes(model.hd(jax.numpy.asarray(x), dtype), t))


def test_bfloat16_control_fails_at_published_widths():
    """bfloat16 keeps 8 significant bits: the stream's integers above
    256 round, and signs downstream flip, on one row."""
    model = C.build(SEEDS[0])
    x = _rows(model, 1)
    want = _ref_votes(model, x)
    ctl = _ref_votes(model, x, "bfloat16")
    assert check.wrong_rows(ctl, want) > check.LIMITS["wrong_rows"]


@pytest.fixture(scope="module", params=SEEDS, ids=["seed0", "seed1"])
def small(request):
    return C.build(request.param, SMALL)


def _rows(model, n):
    return model.rows(np.random.default_rng(M.seeds(SEEDS[0])["traffic"]),
                      n)


def _votes(model, x, how, tmp_path):
    from repro.deploy import Deployment
    from repro.kernels import ref
    from repro.serve.picbnn import BatchingPolicy, PicBnnServer
    from repro.spec import InferenceSpec

    dep = model.deployment
    if how == "run":
        return np.asarray(dep.run(x, InferenceSpec()))
    if how == "loaded":
        back = Deployment.load(dep.save(tmp_path / "d"))
        return np.asarray(back.run(x, InferenceSpec()))
    if how == "oracle":
        return np.asarray(ref.conv_votes_ref(
            list(dep.folded), dep.pipeline().head, x, dep.image_encoding,
            dep.image_side, dep.image_channels))
    srv = PicBnnServer(BatchingPolicy(max_batch=32, max_wait_us=200))
    srv.register("reactnet", dep)
    with srv:
        res = srv.submit_many("reactnet", x).results(timeout=120)
    return np.stack([r.votes for r in res])


@pytest.mark.parametrize("how", ["run", "server", "loaded", "oracle"])
def test_reference_agrees_with_the_program(small, how, tmp_path):
    x = _rows(small, 40)
    assert x.dtype == np.float32 and x.min() >= 0 and x.max() <= 1
    votes = _votes(small, x, how, tmp_path)
    want = check.reference_votes(small, x)
    assert votes.shape == want.shape == (40, 10)
    assert check.wrong_rows(votes, want) == 0
    assert len(np.unique(votes)) > 3


def test_control_in_lower_precision_is_caught_at_a_small_size(small):
    """At this size the stream's values are small enough that bfloat16
    rarely flips a sign (8 of 2,048 rows on one seed, none on the other):
    float8 is caught on every row."""
    x = _rows(small, 32)
    want = check.reference_votes(small, x)
    ctl = check.reference_votes(small, x, "float8_e4m3fn")
    assert check.wrong_rows(ctl, want) > check.LIMITS["wrong_rows"]


def test_weights_reach_the_program_in_deployment_form(small):
    """The magnitudes, constants, RSign and RPReLU integers and shortcuts
    are the file's, and each doubling 1x1 is one c -> 2c conv."""
    convs = small.deployment.conv_layers
    assert [l.shortcut for l in convs] == [
        s.shortcut for s in C.cnn_config(SMALL).conv]
    assert convs[0].alpha is None and convs[0].rprelu is None
    for layer in convs:
        assert set(np.unique(layer.residual)) <= {1, 2, 3, 4}
        assert np.abs(layer.c).max() <= SMALL["c_max"]
        if layer.shortcut == "dup":
            assert layer.k == 1 and layer.c_out == 2 * layer.c_in
        if layer is convs[0]:
            continue
        assert np.abs(layer.alpha).max() <= SMALL["c_max"]
        rp = layer.rprelu
        assert np.abs(np.concatenate([rp.gamma, rp.zeta])).max() \
            <= SMALL["c_max"]
        assert 0 <= rp.q.min() and rp.q.max() <= SMALL["q_max"]


def _small_module(monkeypatch):
    load = R.load_module

    def patched(path):
        mod = load(path)
        if path.stem != "reactnet_a_imagenet":
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
    res, _ = R.run(CELL, SEEDS[0], 0.4, False, devices=jax.devices()[:1],
                   mix_override={"batch": 64})
    assert res["correct"] is (not altered)
    assert set(res["metrics"]) == {"offline_inf_per_s", "setup_s"}
    json.dumps(res)


def _metric(name):
    return R.load_module(R.BENCH / "metrics" / f"{name}.py")


def test_roofline_reads_the_vote_program_time(small):
    """The program's time is the device's busy time less other programs'
    top ops: its own fusions outnumber the ten top ops the trace keeps."""
    kind = "TPU v5 lite"
    top = [["jit_picbnn_votes_off/%or_convert_fusion.2", 2e-3],
           ["jit_picbnn_votes_off/fusion", 1e-3],
           ["jit_picbnn_pack/fusion", 4e-3]]
    ctx = types.SimpleNamespace(
        model=small, mix={"batch": 64}, device_kind=kind,
        window={"answered": 640}, trace={"top_ops": top, "busy_s": 1e-2})
    least, _ = peaks.least_time_s(small.kernel_ops(640),
                                  small.kernel_bytes(640, 10), kind)
    # at published widths a call of 256 rows is bound by its int8 ops
    published = peaks.least_time_s(
        256 * C.ops_per_row(),
        256 * (C.in_bits_per_row() / 8 + 4 * 1_000) + C.weight_bits() / 8,
        kind)
    assert published[1] == "compute"
    read = _metric("picbnn_votes_off_roofline.reactnet_a").read
    # 6 ms of the program: 3 ms in its top ops, 3 ms in ops past them
    assert read(ctx) == pytest.approx(100.0 * least / 6e-3)
    ctx.trace = {"top_ops": [["jit_picbnn_pack/fusion", 1.0]],
                 "busy_s": 1.0}
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_stored_stream_bytes_per_row_read_the_program_counters(
        small, monkeypatch):
    from repro import obs
    from repro.spec import InferenceSpec

    read = _metric("kernel.stream_stored_bytes_per_inf.reactnet_a").read
    assert read(types.SimpleNamespace(trace=None)) is None
    x = _rows(small, 64)  # fills its bucket: every streamed row answered
    small.deployment.run(x, InferenceSpec())
    before = obs.counters()
    small.deployment.run(x, InferenceSpec())
    after = obs.counters()
    # the small net's stream bound is above 32,767 too: int32 throughout
    per_row = 4 * C.stream_elements_per_row(SMALL)
    assert after["kernel.stream_stored_bytes"] \
        - before["kernel.stream_stored_bytes"] == 64 * per_row
    assert after["kernel.rows"] - before["kernel.rows"] == 64
    counts = {"kernel.stream_stored_bytes": 3 * 64 * per_row,
              "kernel.rows": 192}
    monkeypatch.setattr(obs, "counters", lambda: dict(counts))
    assert read(types.SimpleNamespace(trace={})) == per_row
    # a program that keeps no stored-stream counter: nothing
    counts.pop("kernel.stream_stored_bytes")
    assert read(types.SimpleNamespace(trace={})) is None
