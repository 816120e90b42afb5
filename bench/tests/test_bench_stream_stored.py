"""The reader of `kernel.stream_stored_bytes_per_inf.bireal18`: the bytes
the vote program stores for the residual stream per row answered, from
the program's counters, on bireal18_cifar10 cut to a size the host runs;
None without a trace and where the program keeps no such counter."""

import copy
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from bench import model as M, run as R  # noqa: E402
from bench.configs import bireal18_cifar10 as C  # noqa: E402

NAME = "kernel.stream_stored_bytes_per_inf.bireal18"
# Bi-Real-18's pattern at 16x16x3, 8/16/32/64 channels, one basic block a
# stage: every stream's proven bound fits int16
SMALL = copy.deepcopy(C.CONFIG)
SMALL.update(side=16, stem=8, stages=[8, 16, 32, 64], blocks_per_stage=1,
             global_pool=2)


def _read():
    return R.load_module(R.BENCH / "metrics" / f"{NAME}.py").read


def _ctx(trace):
    return types.SimpleNamespace(trace=trace)


def test_the_reader_reads_the_program_counters(monkeypatch):
    from repro import obs
    from repro.spec import InferenceSpec

    read = _read()
    model = C.build(2 ** 31 + 4243, SMALL)
    rng = np.random.default_rng(M.seeds(7)["traffic"])
    x = model.rows(rng, 64)  # fills its bucket: every stored row answered
    model.deployment.run(x, InferenceSpec())
    before = obs.counters()
    model.deployment.run(x, InferenceSpec())
    after = obs.counters()
    pipe = model.deployment.pipeline()
    # int16 throughout at this size: half the stream's 4-B bytes
    per_row = 2 * C.stream_elements_per_row(SMALL)
    assert pipe.stream_stored_bytes_per_row == per_row
    assert pipe.stream_bytes_per_row == 2 * per_row
    assert (after["kernel.stream_stored_bytes"]
            - before["kernel.stream_stored_bytes"]) == 64 * per_row
    assert after["kernel.rows"] - before["kernel.rows"] == 64
    counts = {"kernel.stream_stored_bytes": 3 * 64 * per_row,
              "kernel.rows": 192}
    monkeypatch.setattr(obs, "counters", lambda: dict(counts))
    assert read(_ctx({})) == per_row
    assert read(_ctx(None)) is None  # an untraced run reads nothing


@pytest.mark.parametrize("counts", [
    {"kernel.stream_bytes": 8, "kernel.rows": 2},  # a program before it
    {"kernel.stream_stored_bytes": 0, "kernel.rows": 0},  # no call yet
    {},
], ids=["no-counter", "no-rows", "empty"])
def test_the_reader_gives_nothing_without_its_counters(counts, monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "counters", lambda: dict(counts))
    assert _read()(_ctx({})) is None
