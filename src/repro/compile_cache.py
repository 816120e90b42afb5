"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `benchmarks/run.py`,
`examples/picbnn_serve.py`) call `use_compile_cache()` once at start-up;
importing this module changes nothing.  `JAX_COMPILATION_CACHE_DIR`,
when set, names the directory and nothing else is chosen here.
Otherwise the cache lives at the fixed `<repo>/.jax_cache` (listed in
`.gitignore`): a fixed path, never a temporary name, so a later process
finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
