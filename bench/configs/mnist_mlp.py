"""mnist_mlp: the paper's MNIST MLP, 784-128-10 (`mnist_mlp.json`)."""

from __future__ import annotations

import json
from pathlib import Path

from bench import model as M

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())


def ops_per_row() -> int:
    return M.mlp_ops_per_row(CONFIG)


def build(seed: int, **compile_options) -> M.Model:
    return M.mlp(CONFIG, seed, **compile_options)
