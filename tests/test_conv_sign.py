"""`fused_conv.sign_pm1` is the conv program's `where(v >= 0, 1, -1)`.

The unpooled conv layers and the FC layers take their ±1 int8 outputs
from the shift-and-OR sign, so it must equal the predicate form for
every int32 value: the ends of the range, both sides of zero, and a
seeded batch across the range, eager and under jit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fused_conv

INT32 = np.iinfo(np.int32)
EDGES = [INT32.min, INT32.min + 1, -2, -1, 0, 1, INT32.max - 1, INT32.max]


def _where(v):
    return jnp.where(v >= 0, 1, -1).astype(jnp.int8)


@pytest.mark.parametrize("v", EDGES)
def test_sign_matches_where_at_edges(v):
    x = jnp.asarray([v], jnp.int32)
    got = fused_conv.sign_pm1(x)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_where(x)))
    assert int(got[0]) == (1 if v >= 0 else -1)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_sign_matches_where_on_a_seeded_batch(jit):
    rng = np.random.default_rng(13)
    x = jnp.asarray(np.concatenate([
        rng.integers(INT32.min, INT32.max, 4096, dtype=np.int64,
                     endpoint=True),
        rng.integers(-300, 300, 4096),  # conv sums sit near zero
    ]).astype(np.int32).reshape(8, 32, 32))
    sign = jax.jit(fused_conv.sign_pm1) if jit else fused_conv.sign_pm1
    got = sign(x)
    assert got.dtype == jnp.int8 and got.shape == x.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_where(x)))
