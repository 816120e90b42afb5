"""Operation counts of the configurations and the table of peaks."""

import pytest

import types

from bench import model as M
from bench import peaks
from bench.configs import hg_mlp, mnist_mlp

KIND = "TPU v5 lite"


def test_mac_counts_per_inference_match_hand_counts():
    # 784*128 + 10*(128 + 64)
    assert mnist_mlp.ops_per_row() == 2 * 102_272
    # 4096*128 + 20*(128 + 64)
    assert hg_mlp.ops_per_row() == 2 * 528_128


def _ctx(answered, calls):
    model = M.Model(name="m", deployment=None, n_in=784, n_classes=10,
                    kernel="fused_mlp", ops_per_row=2 * 102_272,
                    weight_bits=102_272, in_bits_per_row=784, rows=None,
                    hd=None, thresholds=None)
    trace = {"kernels": {"fused_mlp": calls}}
    return types.SimpleNamespace(
        model=model, trace=trace, device_kind=KIND, chips=1,
        window={"answered": answered, "completed_in_window": answered,
                "seconds": 1.0})


def test_roofline_counts_answered_rows_not_padding():
    """Two calls of a 128-row bucket that carried 64 and 100 rows: the
    work is 164 rows, and the weights are read once per call."""
    calls = [(128, 2e-6), (128, 2e-6)]
    least, bound = peaks.least_time_s(164 * 2 * 102_272,
                                      164 * (98 + 40) + 2 * 102_272 / 8,
                                      KIND)
    assert bound == "compute"
    got = peaks.kernel_roofline(_ctx(164, calls), "fused_mlp")
    assert got == 100.0 * least / 4e-6
    assert peaks.kernel_roofline(_ctx(164, calls), "fused_conv") is None
    assert peaks.kernel_roofline(_ctx(164, []), "fused_mlp") is None


def test_step_mfu_over_the_window():
    ctx = _ctx(1000, [(128, 1e-6)])
    assert peaks.step_mfu_rate(ctx) == 100.0 * 1000 * 2 * 102_272 / 393e12


def test_least_time_takes_the_larger_bound():
    kind = "TPU v5 lite"
    t, bound = peaks.least_time_s(393e12, 1.0, kind)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.least_time_s(1.0, 2 * 819e9, kind)
    assert (t, bound) == (pytest.approx(2.0), "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.least_time_s(1.0, 1.0, "cpu")
