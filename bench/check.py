"""The comparison that decides `correct`: served votes against the reference.

Each checked request is a pool row.  The reference gives each row its
class vote counts; head distances and thresholds are whole numbers, so
the comparison is exact.  `wrong_rows` counts checked requests with any
class vote that differs from the reference's.

Limits (PERF.md gives the readings they were set from):
  wrong_rows          0   exact comparison
  unanswered          0   every request of the window gets an answer
  compiles_in_window  0   nothing traces or compiles in the window
"""

from __future__ import annotations

import numpy as np

LIMITS = {"wrong_rows": 0, "unanswered": 0, "compiles_in_window": 0}
BLOCK = 2048  # reference rows per device call


def reference_votes(model, x: np.ndarray, dtype=None) -> np.ndarray:
    """int32 [n, C] votes of rows `x` from the plain reference, or with a
    lower-precision `dtype` from the control."""
    import jax
    import jax.numpy as jnp

    from bench.reference import bnn as ref

    hd_fn = jax.jit(model.hd, static_argnums=1)
    t = ref.rounder(dtype)(jnp.asarray(model.thresholds))
    out = []
    for i in range(0, len(x), BLOCK):
        xb = x[i:i + BLOCK]
        n = len(xb)
        if n < BLOCK:  # one block shape: one compile
            xb = np.concatenate([xb, np.repeat(xb[:1], BLOCK - n, 0)])
        out.append(np.asarray(ref.votes(hd_fn(jnp.asarray(xb), dtype),
                                        t))[:n])
    return np.concatenate(out)


def wrong_rows(votes: np.ndarray, want: np.ndarray) -> int:
    """Requests with any class vote other than the reference's."""
    return int((np.asarray(votes) != want).any(-1).sum())


def checks(values: dict) -> dict:
    """{name: {"value", "limit"}} for each compared number."""
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def passed(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
