"""`chip_smoke.py` has no CPU fallback.

Without a TPU it exits non-zero before any work and prints no result
line; and it refuses a pipeline whose program would run the XLA twin
or the Pallas interpreter where the Mosaic kernel was expected.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.paper_mlp import MNIST_MLP, deploy_mlp
from repro.core import bnn
from repro.spec import InferenceSpec

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(SCRIPT)], env=env, capture_output=True,
        text=True, timeout=120, cwd=SCRIPT.parent,
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_refuses_program_without_mosaic_kernel(impl):
    """On the CPU the XLA twin has no kernel, and the interpreted kernel
    lowers to plain XLA: both are refused."""
    smoke = _load_script()
    folded = bnn.random_folded(MNIST_MLP, seed=0)
    pipe = deploy_mlp(MNIST_MLP, folded, impl=impl).pipeline()
    x = np.ones((64, MNIST_MLP.layer_sizes[0]), np.float32)
    with pytest.raises(SystemExit):
        smoke._assert_kernel(pipe, InferenceSpec(), x)
