"""Program spans and counters (repro/obs.py) and where the program
writes them: the pipeline's run, its staging and program names, and the
serving engine's dispatch and completion threads."""

import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs, pipeline
from repro.core import bnn, convnet, ensemble
from repro.core.binarize import InputEncoding
from repro.serve.picbnn import BatchingPolicy, PicBnnServer
from repro.spec import InferenceSpec

SIZES = (96, 32, 5)


@pytest.fixture(scope="module")
def pipe():
    folded = bnn.random_folded(
        bnn.MLPConfig(layer_sizes=SIZES, bias_cells=32), seed=3, cmax=10)
    return pipeline.compile_pipeline(
        folded, ensemble.EnsembleConfig(bias_cells=32),
        min_bucket=8)


def _rows(n):
    rng = np.random.default_rng(n)
    return rng.choice([-1.0, 1.0], (n, SIZES[0])).astype(np.float32)


def _traced(tmp_path, fn):
    """fn() under the profiler; [(name, start, end, stats)] of the
    program's spans in the trace, by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for p in data.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.split(".")[0] in ("picbnn", "serve", "py")),
                  key=lambda s: s[1])


def _delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _run_children(pipe, x, tmp_path):
    """The spans one `run` of `x` writes inside its `picbnn.run`."""
    jax.block_until_ready(pipe.run(x, InferenceSpec()))  # compiled
    spans = _traced(tmp_path, lambda: jax.block_until_ready(
        pipe.run(x, InferenceSpec())))
    (run,) = [s for s in spans if s[0] == "picbnn.run"]
    children = [s for s in spans if s[0] != "picbnn.run"]
    assert all(s[3]["call"] == run[3]["call"] for s in children)
    assert all(run[1] <= s[1] and s[2] <= run[2] for s in children)
    return children


def test_run_spans_carry_the_call_and_nest(pipe, tmp_path):
    # a host batch packs on the host; the packed words are staged
    children = _run_children(pipe, _rows(13), tmp_path)  # 16-row bucket
    assert [s[0] for s in children] == [
        "picbnn.host_pack", "picbnn.stage", "picbnn.pad", "picbnn.vote"]
    assert children[0][3]["rows"] == children[1][3]["rows"] == 13
    assert children[1][3]["bytes"] == 13 * 4 * 3  # 96 bits: 3 words a row


def test_run_spans_of_a_device_array_keep_the_device_pack(pipe, tmp_path):
    # already on the device: nothing staged, the pack program packs it
    children = _run_children(pipe, jax.device_put(_rows(13)), tmp_path)
    assert [s[0] for s in children] == [
        "picbnn.pack", "picbnn.pad", "picbnn.vote"]


@pytest.fixture(scope="module")
def conv_pipe():
    cfg = convnet.CNNConfig(
        side=4, channels=3, encoding=InputEncoding("thermometer", 2),
        conv=(convnet.ConvSpec(3, 8, 1, "same", 2),), hidden=(),
        n_classes=3, bias_cells=32)
    return pipeline.compile_pipeline(
        convnet.random_folded_cnn(cfg, seed=5),
        ensemble.EnsembleConfig(bias_cells=32), image_side=4,
        image_channels=3, min_bucket=8)


@pytest.mark.parametrize("rows", [13, 16], ids=["padded", "full"])
def test_run_spans_of_a_conv_batch_stage_pixels_and_vote(conv_pipe, rows,
                                                         tmp_path):
    # the vote program encodes the staged float32 pixels: no pack program
    x = np.random.default_rng(rows).random((rows, 48)).astype(np.float32)
    children = _run_children(conv_pipe, x, tmp_path)
    names = [s[0] for s in children]
    want = ["picbnn.stage", "picbnn.vote"]
    if rows == 13:  # 16-row bucket
        want.insert(1, "picbnn.pad")
    assert names == want
    assert children[0][3]["bytes"] == rows * 48 * 4


def test_device_pack_calls_count_each_pack_program_dispatch(pipe):
    # an MLP batch already on the device takes the pack program; a host
    # batch is packed on the host and takes none
    xd, xh = jax.device_put(_rows(16)), _rows(16)
    jax.block_until_ready(pipe.run(xd, InferenceSpec()))
    before = obs.counters()
    for _ in range(3):
        jax.block_until_ready(pipe.run(xd, InferenceSpec()))
    assert _delta(before, obs.counters())["pack.device_calls"] == 3
    before = obs.counters()
    jax.block_until_ready(pipe.run(xh, InferenceSpec()))
    assert "pack.device_calls" not in _delta(before, obs.counters())


def test_run_packed_opens_its_own_run_span(pipe, tmp_path):
    xp = pipe._pack_fn(_rows(16))
    jax.block_until_ready(pipe.run_packed(xp, InferenceSpec()))
    spans = _traced(tmp_path, lambda: jax.block_until_ready(
        pipe.run_packed(xp, InferenceSpec())))
    assert [s[0] for s in spans] == ["picbnn.run", "picbnn.vote"]
    assert spans[0][3]["call"] == spans[1][3]["call"]


def test_stage_counts_host_bytes_and_rows_and_passes_device_arrays():
    x = _rows(24)
    before = obs.counters()
    xd = obs.stage(x)
    got = _delta(before, obs.counters())
    assert got["stage.bytes"] == 4 * 24 * SIZES[0]
    assert (got["stage.rows"], got["stage.calls"]) == (24, 1)
    assert got["stage.ns"] > 0
    np.testing.assert_array_equal(np.asarray(xd), x)
    before = obs.counters()
    assert obs.stage(xd) is xd
    assert _delta(before, obs.counters()) == {}


def test_count_adds_and_snapshots_are_copies():
    snap = obs.counters()
    obs.count("test.obs", 2)
    obs.count("test.obs")
    assert _delta(snap, obs.counters()) == {"test.obs": 3}
    obs.counters()["test.obs"] = -1
    assert _delta(snap, obs.counters()) == {"test.obs": 3}


def test_program_names_are_fixed_per_spec(pipe):
    specs = [InferenceSpec(), InferenceSpec(reduction="argmax"),
             InferenceSpec(cumulative=True),
             InferenceSpec(noise="per_request", mc_samples=16,
                           reduction="sum")]
    names = [s.program_name for s in specs]
    assert names == ["picbnn_votes_off", "picbnn_votes_off_argmax",
                     "picbnn_votes_off_cumulative",
                     "picbnn_votes_per_request_mc16_sum"]
    x = jax.ShapeDtypeStruct((8, 3), np.uint32)
    for spec in specs[:3]:
        text = pipe.program(spec).lower(x).as_text()
        assert f"module @jit_{spec.program_name} " in text
    text = pipe._pack_fn.lower(jax.ShapeDtypeStruct((8, SIZES[0]),
                                                    np.float32)).as_text()
    assert "module @jit_picbnn_pack " in text


def _server(pipe):
    srv = PicBnnServer(BatchingPolicy(max_batch=16, max_wait_us=200,
                                      max_inflight=2))
    srv.register("m", pipe, warmup=True)
    return srv


def test_server_stages_its_logical_rows_not_the_bucket(pipe):
    with _server(pipe) as srv:
        before = obs.counters()
        srv.submit_many("m", _rows(11)).wait_all(timeout=60)
        got = _delta(before, obs.counters())
    assert got["stage.rows"] == 11
    assert got["stage.bytes"] == 4 * 16 * SIZES[0]  # the 16-row bucket


def test_server_spans_share_each_batch_id(pipe, tmp_path):
    srv = _server(pipe).start()

    def serve():  # closing joins both threads: every span has ended
        srv.submit_many("m", _rows(40)).wait_all(timeout=60)
        srv.close()

    try:
        spans = _traced(tmp_path, serve)
    finally:
        srv.close()
    ids = {}
    for name, _, _, stats in spans:
        if "batch" in stats:
            ids.setdefault(name, set()).add(stats["batch"])
    names = ("serve.assemble", "picbnn.stage", "serve.enqueue",
             "serve.backpressure", "serve.readback")
    assert set(ids) == set(names)
    assert len(ids["serve.assemble"]) == 3  # 40 rows in batches of 16
    assert all(ids[n] == ids["serve.assemble"] for n in names)
    calls = {s[3]["call"] for s in spans if s[0] == "picbnn.vote"}
    assert len(calls) == 3


def test_collector_passes_are_spans_and_counted(tmp_path):
    obs.watch_gc()
    obs.watch_gc()  # idempotent: one callback
    assert gc.callbacks.count(obs._on_gc) == 1
    before = obs.counters()
    spans = _traced(tmp_path, gc.collect)
    got = _delta(before, obs.counters())
    assert got["gc.collections"] >= 1 and got["gc.pause_ns"] > 0
    assert any(s[0] == "py.gc" and s[3]["generation"] == 2 for s in spans)
