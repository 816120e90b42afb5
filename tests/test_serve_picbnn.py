"""Classification serving subsystem (serve/picbnn.py + serve/scheduler.py).

The correctness bar: serving is a SCHEDULING layer — it may coalesce,
pad, reorder, and fan out however it likes, but every served result must
be bit-exact equal to a direct CompiledPipeline call on the same input,
noiseless and seeded-silicon, across the macro's three logical bank
configurations.  Silicon determinism rides the per-request-key specs
(`InferenceSpec(noise="per_request")`, with or without `mc_samples`),
whose batch-composition invariance is itself tested here.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pipeline
from repro.core import bnn, ensemble
from repro.core.device_model import NOISELESS, SILICON
from repro.serve.picbnn import BatchingPolicy, PicBnnServer, QueueFullError
from repro.serve.scheduler import MicroBatcher, latency_summary
from repro.spec import InferenceSpec

# Same bank-configuration nets as tests/test_pipeline.py: head rows land
# on each of the macro's logical row widths (256 / 128 / 64 bits).
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
    return pipeline.compile_pipeline(
        folded, ensemble.EnsembleConfig(bias_cells=bias),
        min_bucket=8, noise=noise, **kw
    ), sizes


def _images(n, n_in, seed=1):
    rng = np.random.default_rng(seed)
    return rng.choice([-1.0, 1.0], (n, n_in)).astype(np.float32)


def _votes(pipe, x):
    """The noise-off votes of a direct pipeline call."""
    return np.asarray(pipe.run(x, InferenceSpec()))


def _votes_each(pipe, x, keys, mc_samples=None):
    """One silicon draw per row from `keys[i]` ([S, B, C] with MC)."""
    spec = InferenceSpec(noise="per_request", mc_samples=mc_samples)
    return np.asarray(pipe.run(x, spec, keys=keys))


# ---------------------------------------------------------------------------
# per-request-key pipeline entries (the silicon serving contract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bank", sorted(BANK_NETS))
def test_votes_each_batch_composition_invariant(bank):
    """A per-request row i depends only on (x_i, keys_i): any batch split —
    including single-request calls, which hit different bucket paddings —
    returns identical votes."""
    pipe, sizes = _make_pipe(bank, noise=SILICON)
    x = _images(21, sizes[0])
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(7), 21))
    full = _votes_each(pipe, x, keys)
    split = np.concatenate([
        _votes_each(pipe, x[:13], keys[:13]),
        _votes_each(pipe, x[13:], keys[13:]),
    ])
    np.testing.assert_array_equal(full, split)
    for i in (0, 11, 20):
        np.testing.assert_array_equal(
            _votes_each(pipe, x[i:i + 1], keys[i:i + 1])[0],
            full[i],
        )
    # a real draw, not the noiseless staircase
    assert (full != _votes(pipe, x)).any()


def test_votes_each_noiseless_limit_and_mc_identity():
    pipe, sizes = _make_pipe("1024x128", noise=NOISELESS)
    x = _images(9, sizes[0])
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), 9))
    np.testing.assert_array_equal(
        _votes_each(pipe, x, keys), _votes(pipe, x)
    )
    si, _ = _make_pipe("1024x128", noise=SILICON)
    mc = _votes_each(si, x, keys, mc_samples=4)  # [S, B, C]
    assert mc.shape[0] == 4
    for s in range(4):
        for i in (0, 8):
            ks = np.asarray(jax.random.split(jnp.asarray(keys[i]), 4))[s]
            np.testing.assert_array_equal(
                mc[s, i],
                _votes_each(si, x[i:i + 1], ks[None])[0],
            )


def test_votes_each_rejects_bad_keys_and_noiseless_pipe():
    pipe, sizes = _make_pipe("2048x64")  # no noise= at all
    x = _images(3, sizes[0])
    with pytest.raises(ValueError, match="noise="):
        _votes_each(pipe, x, np.zeros((3, 2), np.uint32))
    si, _ = _make_pipe("2048x64", noise=SILICON)
    with pytest.raises(ValueError, match="keys"):
        _votes_each(si, x, np.zeros((5, 2), np.uint32))  # wrong B


# ---------------------------------------------------------------------------
# warmup / bucket grid
# ---------------------------------------------------------------------------
def test_next_bucket_guards_and_grid():
    with pytest.raises(ValueError, match=">= 1"):
        pipeline.next_bucket(0, 8)
    with pytest.raises(ValueError, match=">= 1"):
        pipeline.next_bucket(-3, 8)
    with pytest.raises(ValueError, match="max_bucket"):
        pipeline.next_bucket(33, 8, max_bucket=32)
    assert pipeline.next_bucket(32, 8, max_bucket=32) == 32
    assert pipeline.bucket_grid(33, 8) == (8, 16, 32, 64)
    assert pipeline.bucket_grid(1, 8) == (8,)


def test_warmup_covers_bucket_grid():
    pipe, sizes = _make_pipe("2048x64", noise=SILICON, max_bucket=32)
    times = pipe.warmup(32, mc_samples=2)
    # per-(spec, bucket) attribution: every default spec at every bucket
    assert sorted({b for _spec, b in times}) == [8, 16, 32]
    assert {spec for spec, _b in times} == \
        set(pipe.default_warmup_specs(2))
    assert all(t > 0 for t in times.values())
    # warmed entries run without error at every bucket and ragged sizes
    for b in (1, 8, 9, 32):
        x = _images(b, sizes[0])
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(b), b))
        assert _votes_each(pipe, x, keys).shape == (b, sizes[-1])
    with pytest.raises(ValueError, match="max_bucket"):
        _votes(pipe, _images(33, sizes[0]))


# ---------------------------------------------------------------------------
# MicroBatcher policy logic (fake clock — no sleeping)
# ---------------------------------------------------------------------------
def _lots(spans):
    """Flatten dispatched spans to (lot, lo, hi) triples for asserts."""
    return [(s.lot, s.lo, s.hi) for s in spans]


def test_microbatcher_full_batch_dispatches_immediately():
    clock = [0.0]
    mb = MicroBatcher(BatchingPolicy(max_batch=4, max_wait_us=1e6),
                      clock=lambda: clock[0])
    for i in range(9):
        mb.put("m", i)
    lane, spans = mb.next_batch(timeout=0)
    assert lane == "m"
    assert _lots(spans) == [(0, 0, 1), (1, 0, 1), (2, 0, 1), (3, 0, 1)]
    lane, spans = mb.next_batch(timeout=0)
    assert [s.lot for s in spans] == [4, 5, 6, 7]
    # 1 leftover: not full, deadline not reached -> nothing due
    assert mb.next_batch(timeout=0) is None
    assert mb.depth == 1


def test_microbatcher_splits_lots_and_keeps_deadline():
    """A burst larger than max_batch dispatches as consecutive spans of
    one lot; the remainder keeps the ORIGINAL enqueue time (its deadline
    clock must not reset when the front is carved off)."""
    clock = [0.0]
    mb = MicroBatcher(BatchingPolicy(max_batch=4, max_wait_us=2000.0),
                      clock=lambda: clock[0])
    mb.put("m", "burst", size=10)
    lane, spans = mb.next_batch(timeout=0)  # full batch available
    assert _lots(spans) == [("burst", 0, 4)]
    lane, spans = mb.next_batch(timeout=0)
    assert _lots(spans) == [("burst", 4, 8)]
    assert mb.next_batch(timeout=0) is None  # 2 left: partial, not due
    clock[0] = 0.0021  # original enqueue time + 2 ms passed
    lane, spans = mb.next_batch(timeout=0)
    assert _lots(spans) == [("burst", 8, 10)]
    assert mb.depth == 0


def test_microbatcher_deadline_dispatches_partial():
    clock = [0.0]
    mb = MicroBatcher(BatchingPolicy(max_batch=100, max_wait_us=2000.0),
                      clock=lambda: clock[0])
    mb.put("m", "a")
    clock[0] = 0.001  # 1 ms < 2 ms deadline
    mb.put("m", "b")
    assert mb.next_batch(timeout=0) is None
    clock[0] = 0.0021  # oldest request now past its 2 ms deadline
    lane, spans = mb.next_batch(timeout=0)
    assert [s.lot for s in spans] == ["a", "b"]


def test_microbatcher_lanes_never_mix_and_oldest_first():
    clock = [0.0]
    mb = MicroBatcher(BatchingPolicy(max_batch=10, max_wait_us=1000.0),
                      clock=lambda: clock[0])
    mb.put("a", 1)
    clock[0] = 1e-4
    mb.put("b", 2)
    mb.put("a", 3)
    clock[0] = 0.01  # both lanes past deadline; lane "a" is older
    lane, spans = mb.next_batch(timeout=0)
    assert lane == "a" and [s.lot for s in spans] == [1, 3]
    lane, spans = mb.next_batch(timeout=0)
    assert lane == "b" and [s.lot for s in spans] == [2]


def test_microbatcher_full_lane_beats_older_partial():
    clock = [0.0]
    mb = MicroBatcher(BatchingPolicy(max_batch=2, max_wait_us=1e9),
                      clock=lambda: clock[0])
    mb.put("old", 0)
    clock[0] = 1.0  # "old" is older but nowhere near its deadline
    mb.put("full", 1)
    mb.put("full", 2)
    lane, _ = mb.next_batch(timeout=0)
    assert lane == "full"  # dispatching it costs no extra waiting


def test_microbatcher_expired_partial_beats_flooded_full_lane():
    """The bounded-delay contract: a perpetually-full sibling lane must
    not starve a partial batch whose max_wait deadline has expired."""
    clock = [0.0]
    mb = MicroBatcher(BatchingPolicy(max_batch=2, max_wait_us=1000.0),
                      clock=lambda: clock[0])
    mb.put("slow", "victim")
    clock[0] = 0.002  # victim is now past its 1 ms deadline
    mb.put("flood", "burst", size=50)  # always >= max_batch
    lane, spans = mb.next_batch(timeout=0)
    assert lane == "slow" and [s.lot for s in spans] == ["victim"]
    lane, _ = mb.next_batch(timeout=0)  # then the flood drains
    assert lane == "flood"


def test_microbatcher_queue_bound_and_drain_on_close():
    mb = MicroBatcher(BatchingPolicy(max_batch=8, max_wait_us=1e6,
                                     max_queue=3))
    for i in range(3):
        mb.put("m", i)
    with pytest.raises(QueueFullError):
        mb.put("m", 99, block=False)
    with pytest.raises(QueueFullError):  # lot admission is all-or-nothing
        mb.put("m", "burst", size=2, block=False)
    with pytest.raises(QueueFullError):  # a lot that can NEVER fit must
        mb.put("m", "huge", size=4)  # reject even when block=True
    assert mb.high_water == 3
    mb.close()
    with pytest.raises(RuntimeError):
        mb.put("m", 100)
    lane, spans = mb.next_batch()  # close drains partials immediately
    assert [s.lot for s in spans] == [0, 1, 2]
    assert mb.next_batch() is None  # closed + empty


def test_latency_summary_percentiles():
    s = latency_summary(list(range(1, 101)))
    assert (s.n, s.p50_ms, s.max_ms) == (100, 50.5, 100.0)
    assert s.p99_ms > s.p95_ms > s.p50_ms
    assert latency_summary([]).n == 0


# ---------------------------------------------------------------------------
# served results are bit-exact vs direct pipeline calls
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bank", sorted(BANK_NETS))
def test_served_noiseless_bit_exact(bank):
    pipe, sizes = _make_pipe(bank, max_bucket=64)
    x = _images(43, sizes[0], seed=3)
    want_votes = _votes(pipe, x)
    want_pred = want_votes.argmax(-1)
    srv = PicBnnServer(BatchingPolicy(max_batch=16, max_wait_us=200.0))
    srv.register(bank, pipe, layer_sizes=sizes)
    with srv:
        handles = [srv.submit(bank, x[i]) for i in range(len(x))]
        results = [h.result(timeout=60) for h in handles]
    for i, r in enumerate(results):
        assert r.pred == want_pred[i]
        np.testing.assert_array_equal(r.votes, want_votes[i])
        assert r.latency_ms >= r.service_ms >= 0
        assert r.queue_ms >= 0 and 1 <= r.batch_size <= 16
        assert r.bucket in pipe.buckets_for(16)
    st = srv.stats()
    assert st.n_requests == len(x)
    assert st.per_model[bank].silicon_inf_per_s > 0
    assert 0 < st.mean_occupancy <= 1.0


def test_submit_many_burst_bit_exact_and_split_across_batches():
    """A burst bigger than max_batch splits across micro-batches but
    returns one coherent, bit-exact result set (noiseless + silicon)."""
    pipe, sizes = _make_pipe("1024x128", max_bucket=64)
    si, _ = _make_pipe("1024x128", noise=SILICON, max_bucket=64)
    x = _images(41, sizes[0], seed=9)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(5), len(x)))
    srv = PicBnnServer(BatchingPolicy(max_batch=16, max_wait_us=300.0))
    srv.register("n", pipe)
    srv.register("s", si)
    with srv:
        gn = srv.submit_many("n", x)
        gs = srv.submit_many("s", x, keys=keys)
        preds = gn.wait_all(timeout=60)
        votes = gs.votes_all(timeout=60)
        res = gn.results(timeout=60)
    np.testing.assert_array_equal(
        preds, np.asarray(pipe.run(x, InferenceSpec(reduction="argmax"))))
    np.testing.assert_array_equal(votes, _votes_each(si, x, keys))
    assert len(gn) == len(res) == 41
    # burst of 41 with max_batch 16 -> split across >= 3 micro-batches
    assert len({id(r) for r in res}) == 41
    assert len(gn._slab.spans) >= 3
    uids = [r.uid for r in res]
    assert uids == list(range(uids[0], uids[0] + 41))


@pytest.mark.parametrize("bank", sorted(BANK_NETS))
def test_served_silicon_seeded_bit_exact_any_batching(bank):
    """Per-request keys make silicon serving deterministic: two servers
    with very different coalescing policies return identical, directly-
    reproducible votes."""
    pipe, sizes = _make_pipe(bank, noise=SILICON, max_bucket=64)
    x = _images(29, sizes[0], seed=4)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(11), len(x)))
    want = _votes_each(pipe, x, keys)
    for pol in (BatchingPolicy(max_batch=4, max_wait_us=100.0),
                BatchingPolicy(max_batch=32, max_wait_us=5000.0)):
        srv = PicBnnServer(pol)
        srv.register("si", pipe)
        with srv:
            hs = [srv.submit("si", x[i], key=keys[i])
                  for i in range(len(x))]
            got = np.stack([h.result(timeout=60).votes for h in hs])
        np.testing.assert_array_equal(got, want)


def test_served_mc_model_matches_votes_mc_each():
    pipe, sizes = _make_pipe("2048x64", noise=SILICON, max_bucket=32)
    x = _images(11, sizes[0], seed=5)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(2), len(x)))
    want = _votes_each(pipe, x, keys, mc_samples=5).sum(0)
    srv = PicBnnServer(BatchingPolicy(max_batch=8, max_wait_us=200.0))
    srv.register("mc", pipe, mc_samples=5)
    with srv:
        hs = [srv.submit("mc", x[i], key=keys[i]) for i in range(len(x))]
        res = [h.result(timeout=60) for h in hs]
    np.testing.assert_array_equal(np.stack([r.votes for r in res]), want)
    np.testing.assert_array_equal([r.pred for r in res], want.argmax(-1))


def test_mixed_model_traffic_never_mixes_batches():
    p1, s1 = _make_pipe("1024x128", max_bucket=32)
    p2, s2 = _make_pipe("2048x64", noise=SILICON, max_bucket=32)
    x1 = _images(17, s1[0], seed=6)
    x2 = _images(13, s2[0], seed=7)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(3), len(x2)))
    srv = PicBnnServer(BatchingPolicy(max_batch=8, max_wait_us=300.0))
    srv.register("noiseless", p1, layer_sizes=s1)
    srv.register("silicon", p2, layer_sizes=s2)
    with srv:
        hs = []
        for i in range(max(len(x1), len(x2))):  # interleaved arrival
            if i < len(x1):
                hs.append(("noiseless", i, srv.submit("noiseless", x1[i])))
            if i < len(x2):
                hs.append(("silicon", i,
                           srv.submit("silicon", x2[i], key=keys[i])))
        res = [(m, i, h.result(timeout=60)) for (m, i, h) in hs]
    want1 = _votes(p1, x1)
    want2 = _votes_each(p2, x2, keys)
    for m, i, r in res:
        assert r.model_id == m  # a batch serves exactly one model
        np.testing.assert_array_equal(
            r.votes, want1[i] if m == "noiseless" else want2[i]
        )
    st = srv.stats()
    assert st.per_model["noiseless"].n_requests == len(x1)
    assert st.per_model["silicon"].n_requests == len(x2)


def test_engine_submit_validation():
    pipe, sizes = _make_pipe("2048x64", max_bucket=32)
    si, _ = _make_pipe("2048x64", noise=SILICON, max_bucket=32)
    srv = PicBnnServer(BatchingPolicy(max_batch=8, max_wait_us=100.0))
    srv.register("n", pipe)
    srv.register("s", si)
    with pytest.raises(ValueError, match="mc_samples"):
        srv.register("bad", pipe, mc_samples=3)  # noiseless pipe
    with pytest.raises(ValueError, match="already registered"):
        srv.register("n", pipe)
    img = _images(1, sizes[0])[0]
    with srv:
        with pytest.raises(KeyError, match="unknown model"):
            srv.submit("nope", img)
        with pytest.raises(ValueError, match="PRNG key"):
            srv.submit("s", img)  # silicon without key
        with pytest.raises(ValueError, match="noiseless"):
            srv.submit("n", img, key=np.zeros(2, np.uint32))
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit("n", img)
    # stats() on a server that served nothing must not blow up
    empty = PicBnnServer(BatchingPolicy())
    assert empty.stats().n_requests == 0
    # a max_batch whose BUCKET exceeds the pipeline cap is caught at
    # start(), not on the first full dispatch (24 -> bucket 32 <= 32 ok,
    # 33 -> bucket 64 > 32 rejected even though 33 < ... is non-pow2)
    bad = PicBnnServer(BatchingPolicy(max_batch=33, max_wait_us=100.0))
    bad.register("n", pipe)  # pipe has max_bucket=32
    with pytest.raises(ValueError, match="bucket"):
        bad.start()
    from repro.serve import GroupHandle  # lazy public surface resolves
    assert GroupHandle is not None


def test_engine_queue_full_and_drain_on_close():
    pipe, sizes = _make_pipe("2048x64", max_bucket=32)
    x = _images(6, sizes[0], seed=8)
    want = _votes(pipe, x).argmax(-1)
    # deadline far away + batch bigger than the stream: the batcher holds
    # everything, so admission (max_queue=4) fills deterministically
    srv = PicBnnServer(BatchingPolicy(max_batch=32, max_wait_us=30e6,
                                      max_queue=4))
    srv.register("m", pipe)
    srv.start()
    hs = [srv.submit("m", x[i]) for i in range(4)]
    with pytest.raises(QueueFullError):
        srv.submit("m", x[4], block=False)
    with pytest.raises(QueueFullError):
        srv.submit("m", x[4], timeout=0.01)
    srv.close()  # close() flushes the held partial batch
    got = [h.result(timeout=30).pred for h in hs]
    np.testing.assert_array_equal(got, want[:4])


def test_lm_engine_per_request_timing():
    """serve/engine.py Results carry per-request queue/service times in
    the shared metrics vocabulary (not just batch-level phase timings)."""
    from repro import configs
    from repro.models import model as M
    from repro.serve.engine import Engine, EngineConfig, Request

    cfg = configs.get_config("llama3.2-1b+smoke")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(max_batch=2, eos_id=-1))
    rng = np.random.default_rng(0)
    reqs = [
        Request(uid=i, prompt=rng.integers(1, 100, 6).astype(np.int32),
                max_new_tokens=2 if i == 0 else 5)
        for i in range(3)
    ]
    out = eng.generate(reqs)
    for r in out:
        assert r.service_ms > 0 and r.queue_ms >= 0
        assert r.latency_ms == pytest.approx(r.queue_ms + r.service_ms)
    # same batch, fewer tokens -> request 0 finishes no later than 1
    assert out[0].service_ms <= out[1].service_ms
    # batch 2 (request uid=2) queues behind batch 1
    assert out[2].queue_ms >= out[0].queue_ms


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + os.environ.get("XLA_FLAGS", "")
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, numpy as np, jax.numpy as jnp
    from repro import pipeline
    from repro.core import bnn, ensemble
    from repro.serve.picbnn import PicBnnServer, BatchingPolicy
    from repro.spec import InferenceSpec

    assert jax.device_count() == 4
    rng = np.random.default_rng(0)
    sizes, bias = (96, 32, 5), 32
    layers = []
    for i in range(len(sizes) - 1):
        n_in, n_out = sizes[i], sizes[i + 1]
        c = bnn.parity_adjust_c(
            rng.integers(-bias, bias + 1, n_out), n_in, bias)
        layers.append(bnn.FoldedLayer(
            weights_pm1=rng.choice([-1, 1], (n_out, n_in)).astype(np.int8),
            c=c))
    pipe = pipeline.compile_pipeline(
        layers, ensemble.EnsembleConfig(bias_cells=bias),
        min_bucket=8, max_bucket=64)
    x = rng.choice([-1.0, 1.0], (40, sizes[0])).astype(np.float32)
    want = np.asarray(pipe.run(x, InferenceSpec(reduction="argmax")))
    for fanout in ("round_robin", "spmd"):
        srv = PicBnnServer(
            BatchingPolicy(max_batch=8, max_wait_us=200.0), fanout=fanout)
        srv.register("m", pipe)
        srv.warmup()  # covers device- and sharding-targeted warmup
        with srv:
            hs = [srv.submit("m", x[i]) for i in range(len(x))]
            res = [h.result(timeout=60) for h in hs]
        np.testing.assert_array_equal([r.pred for r in res], want)
        if fanout == "round_robin":
            # the ring actually fanned batches out across devices
            assert len({r.device for r in res}) > 1, \\
                sorted({r.device for r in res})
    print("MULTIDEV-OK")
""")


def test_multi_device_fanout_subprocess():
    """Data-parallel fan-out on a forced 4-device host platform: both
    round-robin and SPMD fan-out serve bit-exact predictions, and the
    round-robin ring really spreads batches across devices.  Runs in a
    subprocess because device count is fixed at jax init."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": src},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MULTIDEV-OK" in proc.stdout
