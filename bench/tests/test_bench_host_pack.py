"""The reader of the program's host-pack counters."""

import dataclasses

import pytest

pytest.importorskip("jax")

from bench.run import BENCH, load_module  # noqa: E402

NAME = "pipeline.host_pack_ms_per_batch.offline"


@dataclasses.dataclass
class _Ctx:
    trace: object


def _reader():
    return load_module(BENCH / "metrics" / f"{NAME}.py")


def test_host_pack_reader_is_none_untraced():
    assert _reader().read(_Ctx(trace=None)) is None


def test_host_pack_reader_is_none_without_counters(monkeypatch):
    from repro import obs

    # a program that packs on the device counts only its staging
    monkeypatch.setattr(obs, "counters", lambda: {
        "stage.bytes": 16384, "stage.rows": 1, "stage.calls": 1,
        "stage.ns": 1})
    assert _reader().read(_Ctx(trace={})) is None
    monkeypatch.setattr(obs, "counters", dict)
    assert _reader().read(_Ctx(trace={})) is None


def test_host_pack_reader_gives_ms_per_pack(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "counters", lambda: {
        "pack.host_calls": 4, "pack.host_rows": 4 * 2048,
        "pack.host_ns": 4 * 650_000})
    assert _reader().read(_Ctx(trace={})) == pytest.approx(0.65)
