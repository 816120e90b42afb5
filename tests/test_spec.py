"""InferenceSpec + CompiledPipeline.run: the compiled-request redesign.

Three bars:
  * spec VALIDATION — every unsupported combination is a construction-
    time ValueError; the noiseless staircase is the explicit spec
    `InferenceSpec(noise="off", cumulative=True)`, never a hidden key;
  * run() SEMANTICS — bit-exact against the digital oracles across the
    macro's three logical bank configurations, plus centralized
    key/keys validation and per-spec program caching (the silicon
    specs' noiseless limit and draw-for-draw twin are in
    tests/test_pipeline.py, across both input forms);
  * BUCKETING properties — hypothesis property tests for
    `next_bucket` / `bucket_grid` (grid membership, monotonicity,
    max_bucket caps), via the tests/_hypothesis_compat.py guard.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on environment
    from _hypothesis_compat import given, settings, st

    HAVE_HYPOTHESIS = False

settings.register_profile("ci", max_examples=100, deadline=None)
settings.load_profile("ci")

from repro import pipeline
from repro.core import bnn, ensemble
from repro.core.device_model import NOISELESS, SILICON
from repro.spec import InferenceSpec

BANK_NETS = {
    "512x256": (300, 192, 12),
    "1024x128": (784, 64, 10),
    "2048x64": (96, 32, 5),
}
BANK_BIAS = {"512x256": 64, "1024x128": 64, "2048x64": 32}


def _random_folded(sizes, seed, bias_cells):
    rng = np.random.default_rng(seed)
    layers = []
    for i in range(len(sizes) - 1):
        n_in, n_out = sizes[i], sizes[i + 1]
        c = bnn.parity_adjust_c(
            rng.integers(-bias_cells, bias_cells + 1, n_out), n_in, bias_cells
        )
        layers.append(bnn.FoldedLayer(
            weights_pm1=rng.choice([-1, 1], (n_out, n_in)).astype(np.int8),
            c=c,
        ))
    return layers


def _make_pipe(bank, noise=None, **kw):
    sizes, bias = BANK_NETS[bank], BANK_BIAS[bank]
    folded = _random_folded(sizes, seed=sum(map(ord, bank)), bias_cells=bias)
    pipe = pipeline.compile_pipeline(
        folded, ensemble.EnsembleConfig(bias_cells=bias),
        min_bucket=8, noise=noise, **kw
    )
    return pipe, folded, sizes


def _oracle_votes(folded, head, x):
    h = x
    for layer in folded[:-1]:
        y = h @ jnp.asarray(layer.weights_pm1.T, jnp.float32) + jnp.asarray(
            layer.c, jnp.float32
        )
        h = jnp.where(y >= 0, 1.0, -1.0)
    return ensemble.votes_fused(head, h)


def _images(n, n_in, seed=1):
    rng = np.random.default_rng(seed)
    return rng.choice([-1.0, 1.0], (n, n_in)).astype(np.float32)


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------
def test_spec_defaults_and_derived_contract():
    s = InferenceSpec()
    assert (s.noise, s.mc_samples, s.reduction, s.cumulative) == \
        ("off", None, "none", False)
    assert not s.needs_physics and not s.needs_key and not s.needs_keys
    assert s.batch_axis == 0
    assert InferenceSpec(noise="batch").needs_key
    assert InferenceSpec(noise="per_request").needs_keys
    # leading samples / passes axes shift the batch axis
    assert InferenceSpec(noise="batch", mc_samples=4).batch_axis == 1
    assert InferenceSpec(cumulative=True).batch_axis == 1
    assert InferenceSpec(noise="per_request", mc_samples=4,
                         reduction="sum").batch_axis == 0
    assert InferenceSpec(reduction="argmax").batch_axis == 0
    # hashable values: usable as program-cache / warmup-report keys
    assert InferenceSpec() in {InferenceSpec()}
    assert "noise=batch" in InferenceSpec(noise="batch").describe()


@pytest.mark.parametrize("bad", [
    dict(noise="nope"),
    dict(reduction="mean"),
    dict(mc_samples=0, noise="batch"),
    dict(mc_samples=4),  # MC over a deterministic compare
    dict(reduction="sum"),  # nothing to sum without MC
    dict(noise="batch", mc_samples=4, reduction="argmax"),
    dict(cumulative=True, noise="batch", mc_samples=4),
    dict(cumulative=True, reduction="argmax"),
    dict(cumulative=True, noise="per_request"),
])
def test_spec_rejects_unsupported_combinations(bad):
    with pytest.raises(ValueError):
        InferenceSpec(**bad)


# ---------------------------------------------------------------------------
# run() semantics vs the digital oracles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bank", sorted(BANK_NETS))
def test_run_noiseless_specs_bit_exact(bank):
    pipe, folded, sizes = _make_pipe(bank)
    x = jnp.asarray(_images(23, sizes[0]))
    want = np.asarray(_oracle_votes(folded, pipe.head, x))
    got = np.asarray(pipe.run(x, InferenceSpec()))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(pipe.run(x, InferenceSpec(reduction="argmax"))),
        want.argmax(-1),
    )
    # the explicit noiseless staircase: valid without any physics at all
    cum = np.asarray(pipe.run(x, InferenceSpec(cumulative=True)))
    np.testing.assert_array_equal(cum[-1], want)
    np.testing.assert_array_equal(
        cum,
        np.asarray(ensemble.sweep_from_votes(jnp.asarray(want),
                                             cum.shape[0])),
    )


def test_run_key_and_keys_validation():
    pipe, _folded, sizes = _make_pipe("2048x64", noise=SILICON)
    npipe, _f, _s = _make_pipe("2048x64")
    x = _images(5, sizes[0])
    key = jax.random.PRNGKey(0)
    keys = np.asarray(jax.random.split(key, 5))
    # deterministic spec takes no randomness
    with pytest.raises(ValueError, match="neither key= nor keys="):
        pipe.run(x, InferenceSpec(), key=key)
    # batch spec: key required, keys rejected
    with pytest.raises(ValueError, match="explicit key="):
        pipe.run(x, InferenceSpec(noise="batch"))
    with pytest.raises(ValueError, match="not per-request keys="):
        pipe.run(x, InferenceSpec(noise="batch"), keys=keys)
    # per-request spec: keys required (right shape), key rejected
    with pytest.raises(ValueError, match="needs per-request keys="):
        pipe.run(x, InferenceSpec(noise="per_request"))
    with pytest.raises(ValueError, match="not a batch-level key="):
        pipe.run(x, InferenceSpec(noise="per_request"), key=key, keys=keys)
    with pytest.raises(ValueError, match="keys must be"):
        pipe.run(x, InferenceSpec(noise="per_request"), keys=keys[:3])
    # physics-requiring specs fail loudly on a noiseless-compiled pipeline
    with pytest.raises(ValueError, match="noise="):
        npipe.run(x, InferenceSpec(noise="batch"), key=key)
    with pytest.raises(ValueError, match="noise="):
        npipe.warmup(8, specs=(InferenceSpec(noise="per_request"),))


def test_cum_votes_shim_explicit_key_contract():
    """No hidden PRNGKey(0) anywhere: a silicon staircase needs its key,
    and the noiseless staircase is an explicit deterministic spec."""
    # silicon pipeline: the batch-draw staircase without key= fails loudly
    si, _f, sizes = _make_pipe("2048x64", noise=SILICON)
    x = _images(4, sizes[0])
    with pytest.raises(ValueError, match="explicit key"):
        si.run(x, InferenceSpec(noise="batch", cumulative=True))
    # NOISELESS physics and no physics at all give the same noiseless
    # staircase, whose last pass is the plain votes
    nl, _folded, _ = _make_pipe("2048x64", noise=NOISELESS)
    plain, _f2, _s2 = _make_pipe("2048x64")
    want = np.asarray(plain.run(x, InferenceSpec(cumulative=True)))
    np.testing.assert_array_equal(
        np.asarray(nl.run(x, InferenceSpec(cumulative=True))), want)
    np.testing.assert_array_equal(
        want[-1], np.asarray(plain.run(x, InferenceSpec())))


def test_program_cache_one_program_per_spec():
    pipe, _folded, sizes = _make_pipe("2048x64", noise=SILICON)
    s1 = InferenceSpec(noise="per_request")
    s2 = InferenceSpec(noise="per_request", mc_samples=2)
    p1 = pipe.program(s1)
    assert pipe.program(s1) is p1  # cache hit: the SAME compiled program
    assert pipe.program(InferenceSpec(noise="per_request")) is p1
    assert pipe.program(s2) is not p1  # distinct spec -> distinct program
    assert set(pipe._programs) == {s1, s2}


def test_run_bucketing_invariance_across_specs():
    """Padding to a bucket never changes trimmed results, whatever the
    spec's output layout (leading batch, samples-first, passes-first)."""
    pipe, _folded, sizes = _make_pipe("2048x64", noise=NOISELESS)
    x = _images(21, sizes[0], seed=3)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(1), 21))
    key = jax.random.PRNGKey(2)
    cases = [
        (InferenceSpec(), {}),
        (InferenceSpec(reduction="argmax"), {}),
        (InferenceSpec(cumulative=True), {}),
        (InferenceSpec(noise="batch", mc_samples=2), dict(key=key)),
        (InferenceSpec(noise="per_request"), dict(keys=keys)),
        (InferenceSpec(noise="per_request", mc_samples=2,
                       reduction="sum"), dict(keys=keys)),
    ]
    for spec, kw in cases:
        full = np.asarray(pipe.run(x, spec, **kw))
        ax = spec.batch_axis
        assert full.shape[ax] == 21, (spec, full.shape)
        for b in (1, 8, 13):
            sub_kw = {
                k: (v[:b] if k == "keys" else v) for k, v in kw.items()
            }
            part = np.asarray(pipe.run(x[:b], spec, **sub_kw))
            if spec.noise == "batch":
                # batch-shaped draws are composition-dependent by
                # construction — only shapes are checked
                assert part.shape[ax] == b
            else:
                np.testing.assert_array_equal(
                    part, full[:b] if ax == 0 else full[:, :b]
                )


# ---------------------------------------------------------------------------
# spec-driven warmup
# ---------------------------------------------------------------------------
def test_warmup_reports_per_spec_bucket_and_cache_is_free():
    pipe, _folded, sizes = _make_pipe("2048x64", noise=SILICON,
                                      max_bucket=32)
    specs = (InferenceSpec(noise="per_request"),
             InferenceSpec(noise="per_request", mc_samples=2,
                           reduction="sum"))
    times = pipe.warmup(32, specs=specs)
    assert set(times) == {(s, b) for s in specs for b in (8, 16, 32)}
    assert all(t > 0 for t in times.values())
    # every program is now cached: warming again hits the jit cache and
    # must be far cheaper than the compile pass
    progs = {s: pipe.program(s) for s in specs}
    again = pipe.warmup(32, specs=specs)
    assert set(again) == set(times)
    assert all(pipe.program(s) is p for s, p in progs.items())
    assert sum(again.values()) < 0.5 * sum(times.values())


def test_warmup_defaults_and_legacy_entries():
    """Default warmup: the plain vote program on a noiseless pipeline;
    the batch-draw and per-request programs on a silicon one."""
    pipe, _folded, sizes = _make_pipe("2048x64", max_bucket=16)
    times = pipe.warmup(16)
    assert set(times) == {(InferenceSpec(), 8), (InferenceSpec(), 16)}
    si, _f, _s = _make_pipe("2048x64", noise=SILICON, max_bucket=8)
    assert set(si.warmup(8)) == {
        (InferenceSpec(), 8),
        (InferenceSpec(noise="batch"), 8),
        (InferenceSpec(noise="per_request"), 8),
    }


# ---------------------------------------------------------------------------
# next_bucket / bucket_grid property tests (hypothesis-guarded)
# ---------------------------------------------------------------------------
@given(
    n=st.integers(min_value=1, max_value=4096),
    max_batch=st.integers(min_value=1, max_value=4096),
    min_bucket=st.sampled_from([1, 2, 8, 32, 64, 48]),
)
def test_next_bucket_lands_on_grid(n, max_batch, min_bucket):
    """Every batch 1..max_batch dispatches into a bucket_grid bucket."""
    if n > max_batch:
        n = 1 + n % max_batch
    grid = pipeline.bucket_grid(max_batch, min_bucket)
    b = pipeline.next_bucket(n, min_bucket)
    assert b in grid
    assert b >= n or b == min_bucket
    # grid is the doubling chain from min_bucket covering max_batch
    assert grid[0] == min_bucket and grid[-1] >= max_batch
    assert all(y == 2 * x for x, y in zip(grid, grid[1:]))


@given(
    n=st.integers(min_value=1, max_value=4095),
    min_bucket=st.sampled_from([1, 4, 8, 64]),
)
def test_next_bucket_monotone(n, min_bucket):
    """Buckets are monotone in n (never shrink as the batch grows)."""
    assert (pipeline.next_bucket(n, min_bucket)
            <= pipeline.next_bucket(n + 1, min_bucket))


@given(
    n=st.integers(min_value=1, max_value=4096),
    min_bucket=st.sampled_from([1, 8, 64]),
    cap_pow=st.integers(min_value=0, max_value=7),
)
def test_next_bucket_respects_max_bucket(n, min_bucket, cap_pow):
    """With a cap: either the result is <= cap, or it raises loudly —
    exactly when the uncapped bucket would overshoot."""
    cap = min_bucket * (2 ** cap_pow)
    uncapped = pipeline.next_bucket(n, min_bucket)
    if uncapped <= cap:
        assert pipeline.next_bucket(n, min_bucket, max_bucket=cap) \
            == uncapped
    else:
        with pytest.raises(ValueError, match="max_bucket"):
            pipeline.next_bucket(n, min_bucket, max_bucket=cap)


def test_bucket_property_fallbacks_plain():
    """Plain (non-hypothesis) slice of the same properties, so the
    contract is exercised even where hypothesis is not installed."""
    for min_bucket in (1, 8, 48, 64):
        grid = pipeline.bucket_grid(1000, min_bucket)
        prev = 0
        for n in (1, 2, 7, 8, 9, 63, 64, 65, 500, 1000):
            b = pipeline.next_bucket(n, min_bucket)
            assert b in grid and b >= min(n, b)
            assert b >= prev
            prev = b
    with pytest.raises(ValueError, match="max_bucket"):
        pipeline.next_bucket(65, 64, max_bucket=64)
    assert pipeline.next_bucket(64, 64, max_bucket=64) == 64
