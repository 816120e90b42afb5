"""Whole runs of each driver on the host, with the chip look skipped:
sound runs come out correct, and a run whose answers are altered where
they are produced comes out not correct."""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from bench import run as R  # noqa: E402

SEED = 2 ** 31 + 12345
CASES = {  # cell -> a host-sized mix; every answer is checked
    "hg_mlp.offline_b2048": {"batch": 128},
}
CLOSED = {"driver": "closed_loop", "clients": 2,
          "outstanding": 3, "burst": 64, "pool_rows": 256,
          "check_every": 1,
          "policy": {"max_batch": 64, "max_wait_us": 1000,
                     "max_inflight": 4}}


def _alter_answers(monkeypatch):
    from repro import pipeline

    run_packed = pipeline.CompiledPipeline.run_packed

    def altered(self, *a, **kw):
        out = run_packed(self, *a, **kw)
        return out.at[0, 0].add(1)  # one answer per call, one vote off

    monkeypatch.setattr(pipeline.CompiledPipeline, "run_packed", altered)


def _run(cell):
    res, _ctx = R.run(cell, SEED, 0.4, False, devices=jax.devices()[:1],
                      mix_override=CASES[cell])
    return res


def test_without_a_chip_no_result_and_exit_3(capsys):
    rc = R.main(["--workload", "hg_mlp.offline_b2048", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert rc == 3
    assert capsys.readouterr().out == ""


def test_sound_run_is_correct_and_reports_its_metrics():
    res = _run("hg_mlp.offline_b2048")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"offline_inf_per_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())
    json.dumps(res)


@pytest.mark.parametrize("cell", sorted(CASES))
def test_an_answer_altered_where_produced_is_caught(cell, monkeypatch):
    _alter_answers(monkeypatch)
    res = _run(cell)
    assert res["correct"] is False
    assert res["checks"]["wrong_rows"]["value"] > 0
    assert np.isfinite(res["metrics"]["setup_s"]["value"])


@pytest.mark.parametrize("altered", [False, True], ids=["sound", "altered"])
def test_closed_loop_serving_is_checked(altered, monkeypatch):
    """The closed-loop driver through a real server: sound serving
    matches the reference, and one altered vote per batch is caught."""
    from bench.configs import mnist_mlp
    from bench.drivers import closed_loop

    if altered:
        _alter_answers(monkeypatch)
    model = mnist_mlp.build(SEED)
    drv = closed_loop.Driver(model, CLOSED, jax.devices()[:1], SEED, 0.3)
    drv.setup()
    w = drv.window()
    drv.finish()
    assert w["failed"] == 0 and w["completed_in_window"] > 0
    assert drv.req.n_checked == w["attempted"]
    assert (drv.check() > 0) is altered
