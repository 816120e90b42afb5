"""The plain reference against the program, and the control against the
reference, on a few rows of each configuration."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from bench import check, model as M  # noqa: E402
from bench.configs import hg_mlp, mnist_mlp  # noqa: E402

SEED = 2 ** 33 + 17


@functools.lru_cache(maxsize=None)
def built(config):
    return config.build(SEED)


def _rows(model, n):
    return model.rows(np.random.default_rng(M.seeds(SEED)["traffic"]), n)


@pytest.mark.parametrize("config", [mnist_mlp, hg_mlp],
                         ids=["mnist_mlp", "hg_mlp"])
def test_reference_agrees_with_deployment_run(config):
    from repro.spec import InferenceSpec

    model = built(config)
    x = _rows(model, 24)
    votes = np.asarray(model.deployment.run(x, InferenceSpec()))
    want = check.reference_votes(model, x)
    assert votes.shape == want.shape == (24, model.n_classes)
    assert check.wrong_rows(votes, want) == 0
    # the votes are not all alike: the comparison has something to miss
    assert len(np.unique(votes)) > 3


@pytest.mark.parametrize("config", [mnist_mlp, hg_mlp],
                         ids=["mnist_mlp", "hg_mlp"])
def test_control_in_lower_precision_is_caught(config):
    model = built(config)
    x = _rows(model, 32)
    want = check.reference_votes(model, x)
    ctl = check.reference_votes(model, x, "float8_e4m3fn")
    assert check.wrong_rows(ctl, want) > check.LIMITS["wrong_rows"]


def test_bfloat16_is_exact_for_the_noiseless_mlp():
    """Why the control is fp8: every value on an MLP's path is an
    integer that bfloat16 holds closely enough to keep each sign and each
    head distance, so a bfloat16 reference is the reference."""
    for config in (mnist_mlp, hg_mlp):
        model = built(config)
        x = _rows(model, 64)
        want = check.reference_votes(model, x)
        ctl = check.reference_votes(model, x, "bfloat16")
        assert check.wrong_rows(ctl, want) == 0
