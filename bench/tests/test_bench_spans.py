"""The idle split and the staging readers on a small trace this test
writes itself."""

import dataclasses

import pytest

jax = pytest.importorskip("jax")

from bench import spans as S  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.run import BENCH, load_module  # noqa: E402

# Window 0-10000 ns.  Call 0: stage 100-300, pack span 300-400, vote
# span 400-450; its pack program runs 1000-1500 and its vote program
# 1600-3000.  Call 1: stage 2000-2600, pack span 2600-4000 (the host is
# late), vote span 4000-4100; pack runs 5000-5400, vote 5500-7000.
# Idle gaps: 0-1000 (host wait 0-400, input wait 400-1000), 1500-1600
# (launch, rest), 3000-5000 (host wait 3000-4000, input wait
# 4000-5000), 5400-5500 (rest) and the tail 7000-10000 (rest).
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 1600000 duration_ps: 1400000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 400000 }
    events { metadata_id: 1 offset_ps: 5500000 duration_ps: 1500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 1600000 duration_ps: 1400000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 5500000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fused_mlp.1 = s32[4,20]{1,0} custom-call(u32[4,128]{1,0} %x)" } }
  event_metadata { key: 2 value { id: 2
    name: "%copy.3 = u32[4,128]{1,0} copy(u32[4,128]{0,1} %y)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_picbnn_pack(11)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_picbnn_votes_off(22)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000
      stats { metadata_id: 1 int64_value: 0 }
      stats { metadata_id: 2 int64_value: 8192 }
      stats { metadata_id: 3 int64_value: 4 } }
    events { metadata_id: 3 offset_ps: 300000 duration_ps: 100000
      stats { metadata_id: 1 int64_value: 0 } }
    events { metadata_id: 4 offset_ps: 400000 duration_ps: 50000
      stats { metadata_id: 1 int64_value: 0 } }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 600000
      stats { metadata_id: 1 int64_value: 1 }
      stats { metadata_id: 2 int64_value: 8192 }
      stats { metadata_id: 3 int64_value: 4 } }
    events { metadata_id: 3 offset_ps: 2600000 duration_ps: 1400000
      stats { metadata_id: 1 int64_value: 1 } }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 100000
      stats { metadata_id: 1 int64_value: 1 } } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "picbnn.stage" } }
  event_metadata { key: 3 value { id: 3 name: "picbnn.pack" } }
  event_metadata { key: 4 value { id: 4 name: "picbnn.vote" } }
  stat_metadata { key: 1 value { id: 1 name: "call" } }
  stat_metadata { key: 2 value { id: 2 name: "bytes" } }
  stat_metadata { key: 3 value { id: 3 name: "rows" } }
}
"""


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(d.parents[2])


@pytest.fixture(scope="module")
def parts(trace_dir):
    ops, modules, host = tr.load(trace_dir)
    return ops, modules, S.load_spans(trace_dir), *tr.window_of(host)


def test_spans_keep_their_stats(parts):
    spans = parts[2]
    assert [s.name for s in spans] == [S.STAGE, S.PACK, S.VOTE] * 2
    assert spans[3].ids == {"call": 1, "bytes": 8192, "rows": 4}


def test_busy_waits_and_rest_make_up_the_window(trace_dir):
    got = S.read_dir(trace_dir)["split"]
    assert got["window_s"] == pytest.approx(10e-6)
    assert got["busy_s"] == pytest.approx(3.8e-6)
    assert got["host_wait_s"] == pytest.approx(1.4e-6)
    assert got["input_wait_s"] == pytest.approx(1.6e-6)
    assert got["rest_s"] == pytest.approx(3.2e-6)
    shares = [got[f"{k}_share"] for k in
              ("busy", "host_wait", "input_wait", "rest")]
    assert sum(shares) == pytest.approx(100.0)
    assert (got["runs"], got["runs_before_span"]) == (4, 0)


def test_a_gap_before_the_enqueue_span_ends_is_host_wait(parts):
    ops, modules, spans, t0, t1 = parts
    # the second pack span ends at 4900, not 4000: 900 ns more of the
    # 3000-5000 gap is host wait and as much less input wait
    late = [dataclasses.replace(s, end_ns=4900)
            if s.name == S.PACK and s.ids["call"] == 1 else s
            for s in spans]
    got = S.split(ops, modules, late, t0, t1)
    assert got["host_wait_s"] == pytest.approx(2.3e-6)
    assert got["input_wait_s"] == pytest.approx(0.7e-6)


def test_a_run_before_its_span_is_caught_by_the_clock_check(parts):
    ops, modules, spans, t0, t1 = parts
    early = [dataclasses.replace(s, start_ns=1100)
             if s.name == S.PACK and s.ids["call"] == 0 else s
             for s in spans]
    got = S.split(ops, modules, early, t0, t1)
    assert got["runs_before_span"] == 1
    assert got["max_lead_us"] == pytest.approx(0.1)


def test_mismatched_run_counts_raise(parts):
    ops, modules, spans, t0, t1 = parts
    (dev,) = modules
    fewer = {dev: modules[dev][:-1]}  # the last vote run lost
    with pytest.raises(ValueError, match="picbnn.vote"):
        S.split(ops, fewer, spans, t0, t1)


def test_a_program_without_spans_gives_no_split(parts):
    ops, modules, spans, t0, t1 = parts
    stage_only = [s for s in spans if s.name == S.STAGE]
    assert S.split(ops, modules, stage_only, t0, t1) is None


def test_stage_spans_time_and_bytes_per_row(trace_dir):
    got = S.read_dir(trace_dir)["stage"]
    assert got["stage_spans"] == 2
    assert got["stage_ms_per_batch"] == pytest.approx(0.0004)
    assert got["stage_bytes_per_inf"] == pytest.approx(2048)


@dataclasses.dataclass
class _Ctx:
    trace: object


@pytest.mark.parametrize("name", ["stage.bytes_per_inf.offline",
                                  "pipeline.stage_ms_per_batch.offline"])
def test_staging_readers(name, monkeypatch):
    """None on an untraced run; on a traced one the program's staging
    counters, bytes per staged row and ms per staging call."""
    from repro import obs

    mod = load_module(BENCH / "metrics" / f"{name}.py")
    assert mod.read(_Ctx(trace=None)) is None
    monkeypatch.setattr(obs, "counters", lambda: {
        "stage.bytes": 3 * 16384 * 2048, "stage.rows": 3 * 2048,
        "stage.calls": 3, "stage.ns": 3 * 900_000})
    want = {"stage.bytes_per_inf.offline": 16384,
            "pipeline.stage_ms_per_batch.offline": 0.9}[name]
    assert mod.read(_Ctx(trace={})) == pytest.approx(want)
    monkeypatch.setattr(obs, "counters", dict)
    assert mod.read(_Ctx(trace={})) is None
