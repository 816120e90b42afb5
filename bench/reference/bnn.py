"""Plain reference of the PiC-BNN classifiers, in `jax.numpy` float32.

Written from the paper's equations and the configuration files alone; it
imports nothing of the program under test.  Every binary layer is
sign(W x + C) on ±1 values (Eq. 3, `>= 0` is +1).  The output layer is
the CAM head of Algorithm 1: its C is held by `bias_cells` cells, C
clipped to the cell budget and rounded down to the cells' parity, the
query drives every bias cell, and

    HD_j = (n_in + bias_cells - (W_j h + C_j)) / 2
    votes_j = #{t : HD_j <= T_t},   T_t = (n_in + bias_cells) // 2
                                          - max(sweep) // 2 + sweep_t

`rnd` rounds every stored value to a lower precision for the control;
the reference itself passes `None`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _keep(a):
    return a


# (exponent bits, mantissa bits) of the control's lower precisions
PRECISIONS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}


def rounder(dtype):
    """A function that rounds a float32 array to `dtype`'s precision (None:
    float32, unchanged).  `reduce_precision` rounds on every backend; a
    cast to a narrower type and back may be elided by the compiler."""
    if dtype is None:
        return _keep
    exp, mant = PRECISIONS[dtype]
    return lambda a: jax.lax.reduce_precision(a, exponent_bits=exp,
                                              mantissa_bits=mant)


def head_c(c: np.ndarray, bias_cells: int) -> np.ndarray:
    """C as the head's bias cells hold it: clipped, then rounded down to
    the parity of `bias_cells`."""
    c = np.clip(np.asarray(c, np.int64), -bias_cells, bias_cells)
    return np.where((c + bias_cells) % 2 != 0, c - 1, c)


def head_thresholds(n_in: int, bias_cells: int, sweep) -> np.ndarray:
    """Algorithm 1's sweep centred on the head row's majority point."""
    sweep = np.asarray(sweep, np.int64)
    return ((n_in + bias_cells) // 2 - sweep.max() // 2 + sweep).astype(
        np.float32)


def sign(y):
    return jnp.where(y >= 0, 1.0, -1.0)


def fc_hd(h, fc, bias_cells: int, rnd=_keep):
    """Head Hamming distances [B, C] of ±1 rows `h` [B, n_in].

    fc : [(W ±1 [out, in], C [out])], hidden layers then the output
    layer, whose C is already as the bias cells hold it (`head_c`).
    """
    for w, c in fc[:-1]:
        y = jnp.dot(h, w.astype(jnp.float32).T, precision=HIGHEST)
        h = sign(rnd(y) + c.astype(jnp.float32))
    w, c = fc[-1]
    dot = rnd(jnp.dot(h, w.astype(jnp.float32).T, precision=HIGHEST))
    dot = dot + c.astype(jnp.float32)
    return rnd((w.shape[1] + bias_cells - dot) * 0.5)


def votes(hd, thresholds):
    """Vote counts [B, C]: #{t : HD_j <= T_t}.  Head distances and
    thresholds are whole numbers, so float32 compares them exactly."""
    return (hd[:, None, :] <= thresholds[None, :, None]).sum(1).astype(
        jnp.int32)
