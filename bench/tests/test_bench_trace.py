"""The trace reduction on a small trace this test writes itself."""

import pytest

jax = pytest.importorskip("jax")

from bench import trace as tr  # noqa: E402

# Device ops (ns): a copy 1000-1800 inside the pack program (900-1900);
# fused_mlp 2000-4000 and 3500-5000 (overlapping) inside the vote program
# (1950-5100); a copy 7000-8000 outside any program.  The host window
# runs 1000-11000; the benchmark's wait span covers 5000-6500 and a
# runtime event 8000-10500.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 800000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 3500000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 900000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1950000 duration_ps: 3150000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fused_mlp.1 = s32[2,8,256]{2,1,0} custom-call(s32[4,8,256]{2,1,0} %x)" } }
  event_metadata { key: 2 value { id: 2
    name: "%copy.3 = u32[256,25]{1,0} copy(u32[256,25]{0,1} %y)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_pack(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__votes_off(456)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 1200000 duration_ps: 500000 } }
  lines { id: 8 name: "pjrt" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 2500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.wait" } }
  event_metadata { key: 3 value { id: 3 name: "$frame.py:1 f" } }
  event_metadata { key: 4 value { id: 4 name: "H2D Dispatch" } }
}
"""


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ops, modules, host = tr.load(str(d.parents[2]))
    assert all(not e.name.startswith("$") for e in host)
    return tr.reduce(ops, modules, host, *tr.window_of(host))


def test_window_busy_and_idle(reduced):
    assert reduced["window_s"] == pytest.approx(10e-6)
    # union of [1000, 1800], [2000, 5000] and [7000, 8000]
    assert reduced["busy_s"] == pytest.approx(4.8e-6)
    assert reduced["idle_share"] == pytest.approx(0.52)


def test_kernel_calls_rows_and_time(reduced):
    assert sorted(reduced["kernels"]) == ["fused_mlp"]
    calls = reduced["kernels"]["fused_mlp"]
    assert [rows for rows, _ in calls] == [256, 256]
    assert sum(s for _, s in calls) == pytest.approx(3.5e-6)


def test_top_ops_name_program_and_op(reduced):
    assert dict(reduced["top_ops"]) == pytest.approx({
        "jit__votes_off/fused_mlp": 3.5e-6, "jit_pack/copy": 0.8e-6,
        "?/copy": 1e-6})


def test_idle_gaps_labelled_by_host_event(reduced):
    # gap 5000-7000 overlaps the wait span, 8000-11000 the runtime
    # event, and 1800-2000 only the window and a Python frame
    assert dict(reduced["idle_gaps"]) == pytest.approx({
        "bench.wait": 2e-6, "H2D Dispatch": 3e-6,
        tr.IDLE_HOST: 0.2e-6})


def test_merged_and_gaps_units():
    ev = [tr.Event("a", 0, 5), tr.Event("b", 3, 8), tr.Event("c", 12, 20)]
    busy = tr.merged(ev, 2, 15)
    assert busy == [(2, 8), (12, 15)]
    assert tr.gaps(busy, 0, 18) == [(0, 2), (8, 12), (15, 18)]
    assert tr.op_kernel("%fused_conv.1 = s32[3,8,2048]{2,1,0} custom-call(")\
        == ("fused_conv", (3, 8, 2048))
