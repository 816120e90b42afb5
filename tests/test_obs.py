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
from repro.core import bnn, ensemble
from repro.serve.picbnn import BatchingPolicy, PicBnnServer
from repro.spec import InferenceSpec

SIZES = (96, 32, 5)


@pytest.fixture(scope="module")
def pipe():
    folded = bnn.random_folded(
        bnn.MLPConfig(layer_sizes=SIZES, bias_cells=32), seed=3, cmax=10)
    return pipeline.compile_pipeline(
        folded, ensemble.EnsembleConfig(bias_cells=32), impl="xla",
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


def test_run_spans_carry_the_call_and_nest(pipe, tmp_path):
    x = _rows(13)  # pads to the 16-row bucket
    jax.block_until_ready(pipe.run(x, InferenceSpec()))  # compiled
    spans = _traced(tmp_path, lambda: jax.block_until_ready(
        pipe.run(x, InferenceSpec())))
    (run,) = [s for s in spans if s[0] == "picbnn.run"]
    children = [s for s in spans if s[0] != "picbnn.run"]
    assert [s[0] for s in children] == [
        "picbnn.stage", "picbnn.pack", "picbnn.pad", "picbnn.vote"]
    assert all(s[3]["call"] == run[3]["call"] for s in children)
    assert all(run[1] <= s[1] and s[2] <= run[2] for s in children)
    assert children[0][3]["rows"] == 13


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
