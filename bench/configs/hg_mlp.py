"""hg_mlp: the paper's Hand Gesture MLP, 4096-128-20 (`hg_mlp.json`)."""

from __future__ import annotations

import json
from pathlib import Path

from bench import model as M

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())


def ops_per_row() -> int:
    return M.mlp_ops_per_row(CONFIG)


def build(seed: int, **compile_options) -> M.Model:
    return M.mlp(CONFIG, seed, **compile_options)
