"""PiC-BNN classification serving engine: async micro-batching over the
compiled fused pipeline.

The paper's headline is a *serving* number — 560 K inf/s at 703 M
inf/s/W — and `pipeline.CompiledPipeline` is a bare batch function.
This module is the subsystem between the two: it accepts ragged streams
of single-image requests and turns them into efficiently-bucketed fused
dispatches.

    server = PicBnnServer(BatchingPolicy(max_batch=256, max_wait_us=500))
    server.register("mnist", deployment)    # or a CompiledPipeline, or a
    server.register("hg", "ckpts/hg")       # saved Deployment directory
    server.start()                       # or: with PicBnnServer(...) as s:
    h = server.submit("mnist", image)    # image: [n_in] in the ±1 domain
    res = h.result()                     # .pred, .votes, .latency_ms, ...
    server.close()
    print(server.stats().summary())

Each registered model dispatches through ONE declarative request spec
(`repro.spec.InferenceSpec`), fixed at registration: noiseless models
run `InferenceSpec()`, silicon models the per-request-key spec, MC
models the per-request MC spec with the sum reduction fused in.  The
dispatch hot path is a single `pipe.run(x, spec, keys=...)` — adding a
serving mode is a new spec value, not a new pipeline method.

Architecture (DESIGN.md §9):

  submit()/submit_many() --> MicroBatcher (serve/scheduler.py): requests
      are enqueued as contiguous LOTS (a burst is one lot; a single
      request is a lot of 1), per-model lanes, dispatch on full
      `max_batch` or the `max_wait_us` deadline, bounded admission
      (`max_queue` -> QueueFullError).  The hot path allocates one slab
      per *burst*, never per request — per-request Python cost is what
      caps a GIL-bound serving loop.
  dispatch thread: drains one lane batch (a list of lot spans),
      assembles it into a bucket-sized staging buffer with vectorized
      copies, stages to the next device round-robin (`obs.stage`)
      and issues the jitted pipeline call.  jax dispatch is async, so
      while the device crunches batch N the dispatch thread is already
      assembling and staging batch N+1 (depth bounded by `max_inflight`).
  completion thread: blocks on device->host readback in dispatch order,
      publishes per-batch results, records metrics.

Profiling a live server (`repro.obs`; spans show in a `jax.profiler`
trace, counters in `obs.counters()`): each micro-batch is a `batch=` id
on the dispatch thread's `serve.assemble`, `picbnn.stage` (the
host->device copy, counted in `stage.bytes` / `stage.rows`),
`serve.enqueue` (the pipeline's own `picbnn.*` spans inside) and
`serve.backpressure` (the wait on `max_inflight`), and on the completion
thread's `serve.readback`.  Collector passes show as `py.gc` spans and
in `gc.pause_ns`.

Batches dispatch into the pipeline's power-of-two bucket grid at exactly
bucket-shaped operands, so a server warms O(log max_batch) program
variants per model per device (`CompiledPipeline.warmup`) and never
compiles — not even an eager op — mid-traffic.

Determinism contract: noiseless served predictions are bit-exact equal
to a direct pipeline call on the same images (bucketing is padding-
invariant); silicon-mode requests carry a per-request PRNG key and are
served through the `noise="per_request"` specs (per-request
`batch_shape=()` draws), so results are bit-exact reproducible no matter
how the batcher happens to coalesce the stream — tested on all three
bank configurations in tests/test_serve_picbnn.py.

Device fan-out: round-robin by default — each micro-batch runs whole on
one local device, devices serve independent batches (and different
models) concurrently; the folded weights are jit-closure constants, so
XLA replicates them onto every device that executes the program.  The
explicit-mesh/GSPMD variant (`fanout="spmd"`) shards each batch over a
1-axis local mesh with the batch axis from
`sharding.rules.PICBNN_SERVE_RULES` and weights replicated — better for
latency of big single batches, worse for micro-batch throughput.  A
single-device host is simply the degenerate ring.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from pathlib import Path
from typing import Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.core import mapping
from repro.deploy import Deployment
from repro.pipeline import CompiledPipeline, next_bucket
from repro.spec import InferenceSpec
from repro.serve.scheduler import (
    BatchingPolicy,
    LatencySummary,
    MicroBatcher,
    QueueFullError,
    latency_summary,
)
from repro.sharding import rules as shrules

__all__ = [
    "BatchingPolicy",
    "ClassifyResult",
    "GroupHandle",
    "ModelStats",
    "PicBnnServer",
    "QueueFullError",
    "ServerStats",
]


@dataclasses.dataclass(frozen=True)
class ClassifyResult:
    """One served classification + its per-request timing."""

    uid: int
    model_id: str
    pred: int
    votes: np.ndarray  # [C] int32 (MC models: summed over samples)
    queue_ms: float  # submit -> batch dispatch (coalescing + queueing)
    service_ms: float  # dispatch -> readback complete (staging + compute)
    latency_ms: float  # submit -> readback complete
    batch_size: int  # logical requests in the micro-batch served with
    bucket: int  # padded bucket the batch dispatched into
    device: int  # ring index of the device that served it (-1: spmd)


class _Slab:
    """One admitted burst: contiguous request arrays + placement map.

    `spans` is appended by the dispatch thread as the batcher carves the
    slab into micro-batches: (batch, slab_lo, batch_lo, k) means slab
    rows [slab_lo, slab_lo+k) became rows [batch_lo, batch_lo+k) of
    `batch`.  `placed` counts mapped requests; clients wait on the
    server's dispatch condition until their rows are placed.
    """

    __slots__ = ("uid0", "model_id", "x", "keys", "t_submit", "n",
                 "placed", "spans")

    def __init__(self, uid0: int, model_id: str, x: np.ndarray, keys,
                 t_submit: float):
        self.uid0 = uid0
        self.model_id = model_id
        self.x = x
        self.keys = keys
        self.t_submit = t_submit
        self.n = len(x)
        self.placed = 0
        self.spans: list = []


class _Batch:
    __slots__ = ("model_id", "n", "bucket", "device", "t_dispatch", "t_done",
                 "t_submits", "votes", "preds", "error", "event", "bid")

    def __init__(self, model_id: str, n: int, bucket: int, device: int,
                 t_dispatch: float, t_submits: np.ndarray, bid: int = -1):
        self.model_id = model_id
        self.n = n
        self.bucket = bucket
        self.device = device
        self.t_dispatch = t_dispatch
        self.t_done = 0.0
        self.t_submits = t_submits
        self.votes: Optional[np.ndarray] = None
        self.preds: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.bid = bid  # the `batch=` id of its spans
        self.event = threading.Event()


class GroupHandle:
    """Result handle for one submitted burst (and, via `_Handle`, for
    single requests — a burst of 1).

    Per-request Python cost is the serving throughput ceiling on a
    GIL-bound host, so the group APIs are vectorized: `wait_all` returns
    the [n] prediction array with one event-wait per underlying
    micro-batch; `results` builds the per-request ClassifyResult list
    only when asked.
    """

    __slots__ = ("_slab", "_srv")

    def __init__(self, slab: _Slab, srv: "PicBnnServer"):
        self._slab = slab
        self._srv = srv

    def __len__(self) -> int:
        return self._slab.n

    def done(self) -> bool:
        """True once every request in the burst has a published result."""
        slab = self._slab
        return slab.placed >= slab.n and all(
            b.event.is_set() for (b, _lo, _bp, _k) in slab.spans
        )

    def _wait_placed(self, deadline: Optional[float]) -> None:
        slab = self._slab
        if slab.placed >= slab.n:
            return
        cv = self._srv._dispatch_cv
        with cv:
            while slab.placed < slab.n:
                remaining = (None if deadline is None
                             else deadline - time.perf_counter())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError("request(s) not dispatched in time")
                cv.wait(remaining)

    def _wait_batches(self, timeout: Optional[float]) -> list:
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        self._wait_placed(deadline)
        spans = self._slab.spans
        for batch, _lo, _bp, _k in spans:
            if not batch.event.is_set() and not batch.event.wait(
                None if deadline is None
                else max(deadline - time.perf_counter(), 0.0)
            ):
                raise TimeoutError("batch not completed in time")
            if batch.error is not None:
                raise batch.error
        return spans

    def wait_all(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until every request is served; return preds [n] int."""
        spans = self._wait_batches(timeout)
        slab = self._slab
        if len(spans) == 1 and spans[0][3] == slab.n:
            b, _lo, bp, k = spans[0]
            return b.preds[bp:bp + k]
        preds = np.empty(slab.n, np.int64)
        for batch, lo, bp, k in spans:
            preds[lo:lo + k] = batch.preds[bp:bp + k]
        return preds

    def votes_all(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until served; return vote counts [n, C] int32."""
        spans = self._wait_batches(timeout)
        slab = self._slab
        out = None
        for batch, lo, bp, k in spans:
            if out is None:
                out = np.empty((slab.n, batch.votes.shape[1]),
                               batch.votes.dtype)
            out[lo:lo + k] = batch.votes[bp:bp + k]
        return out

    def _result_at(self, i: int) -> ClassifyResult:
        slab = self._slab
        for batch, lo, bp, k in slab.spans:
            if lo <= i < lo + k:
                j = bp + (i - lo)
                return ClassifyResult(
                    uid=slab.uid0 + i,
                    model_id=batch.model_id,
                    pred=int(batch.preds[j]),
                    votes=batch.votes[j],
                    queue_ms=(batch.t_dispatch - slab.t_submit) * 1e3,
                    service_ms=(batch.t_done - batch.t_dispatch) * 1e3,
                    latency_ms=(batch.t_done - slab.t_submit) * 1e3,
                    batch_size=batch.n,
                    bucket=batch.bucket,
                    device=batch.device,
                )
        raise IndexError(i)  # unreachable after _wait_batches

    def results(self, timeout: Optional[float] = None) -> list:
        """Block until served; return per-request ClassifyResults."""
        self._wait_batches(timeout)
        return [self._result_at(i) for i in range(self._slab.n)]


class _Handle(GroupHandle):
    """Single-request handle (a burst of exactly one)."""

    __slots__ = ()

    def wait(self, timeout: Optional[float] = None) -> int:
        """Block until served; return just the predicted class (the
        no-allocation fast path — result() builds a full dataclass)."""
        return int(self.wait_all(timeout)[0])

    def result(self, timeout: Optional[float] = None) -> ClassifyResult:
        self._wait_batches(timeout)
        return self._result_at(0)


@dataclasses.dataclass
class _Model:
    """Registry entry: compiled pipeline + serving/meta attributes."""

    model_id: str
    pipe: CompiledPipeline
    silicon: bool  # requests must carry a per-request PRNG key
    spec: InferenceSpec  # the ONE spec every dispatch for this model runs
    #   (mc_samples lives inside the spec — no duplicate state)
    silicon_cost: Optional[mapping.InferenceCost]  # Table-II equivalent


@dataclasses.dataclass(frozen=True)
class ModelStats:
    model_id: str
    n_requests: int
    n_batches: int
    mean_batch: float
    mean_occupancy: float  # logical batch / padded bucket (1 = no waste)
    inf_per_s: float  # over this model's active window
    latency: LatencySummary
    queue: LatencySummary
    service: LatencySummary
    silicon_inf_per_s: Optional[float]  # mapping.model_inference_cost
    vs_silicon: Optional[float]  # achieved / silicon-equivalent


@dataclasses.dataclass(frozen=True)
class ServerStats:
    """Aggregate serving report (see summary())."""

    n_requests: int
    n_batches: int
    wall_s: float  # first dispatch -> last completion
    inf_per_s: float
    mean_batch: float
    mean_occupancy: float
    queue_high_water: int
    latency: LatencySummary
    queue: LatencySummary
    service: LatencySummary
    per_model: dict[str, ModelStats]

    def summary(self) -> str:
        """Human-readable multi-line serving report."""
        lines = [
            f"served {self.n_requests} requests in {self.n_batches} "
            f"batches over {self.wall_s:.3f}s -> {self.inf_per_s:,.0f} "
            f"inf/s (mean batch {self.mean_batch:.1f}, occupancy "
            f"{self.mean_occupancy:.2f}, queue high-water "
            f"{self.queue_high_water})",
            f"  latency  {self.latency}",
            f"  queue    {self.queue}",
            f"  service  {self.service}",
        ]
        for ms in self.per_model.values():
            line = (f"  [{ms.model_id}] {ms.n_requests} reqs @ "
                    f"{ms.inf_per_s:,.0f} inf/s, p99 "
                    f"{ms.latency.p99_ms:.3f} ms")
            if ms.silicon_inf_per_s:
                line += (f" — silicon-equivalent {ms.silicon_inf_per_s:,.0f}"
                         f" inf/s (x{ms.vs_silicon:.3f} of Table II)")
            lines.append(line)
        return "\n".join(lines)


class PicBnnServer:
    """Async micro-batching classification server over compiled pipelines.

    Thread model: N client threads call submit()/submit_many(); one
    dispatch thread coalesces + stages + issues jitted calls; one
    completion thread blocks on readbacks and publishes results.
    `close()` drains everything already admitted, then joins both
    threads.
    """

    def __init__(self, policy: BatchingPolicy = BatchingPolicy(), *,
                 devices: Optional[Sequence] = None,
                 fanout: str = "round_robin",
                 stats_window: int = 4096):
        if fanout not in ("round_robin", "spmd"):
            raise ValueError(f"unknown fanout {fanout!r}")
        self.policy = policy
        self.stats_window = stats_window
        self.devices = list(devices) if devices else jax.local_devices()
        self.fanout = fanout
        self._mesh = None
        self._batch_sharding = None
        if fanout == "spmd":
            self._mesh = shrules.serve_mesh(self.devices)
            self._batch_sharding = shrules.batch_sharding(self._mesh)
        self._models: dict[str, _Model] = {}
        self._batcher = MicroBatcher(policy)
        self._inflight: list = []
        self._inflight_cond = threading.Condition()
        # percentile metrics come from a BOUNDED window of recent batch
        # records (each retains its votes array — unbounded retention
        # would leak MB/s at sustained load); counts and the throughput
        # window are tracked as running totals so they stay lifetime-
        # accurate however small the window is
        self._records: "collections.deque[_Batch]" = collections.deque(
            maxlen=stats_window
        )
        self._totals: dict[str, list] = {}  # model -> [n, batches, t0, t1]
        self._records_lock = threading.Lock()
        self._dispatch_cv = threading.Condition()
        self._uid = 0
        self._uid_lock = threading.Lock()
        self._next_dev = 0
        self._batch_ids = itertools.count()
        self._started = False
        self._closed = False
        self._dispatch_t: Optional[threading.Thread] = None
        self._complete_t: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    # registry
    # ------------------------------------------------------------------
    def register(self, model_id: str, model, *,
                 layer_sizes: Optional[Sequence[int]] = None,
                 silicon_cost: Optional[mapping.InferenceCost] = None,
                 mc_samples: int = 0, warmup: bool = False) -> None:
        """Add a model to the registry.

        model : a `CompiledPipeline`, a `deploy.Deployment` (compiled
            lazily), or a str/Path to a SAVED deployment directory
            (`Deployment.save` output — servers register models straight
            from disk).  MLP deployments take ±1 activation requests of
            width `pipe.n_in`, conv deployments raw [0,1] pixel requests
            of width image_side**2; the serving layer only sees [n_in]
            request rows either way.

        layer_sizes : optional (n_in, ..., n_classes) of a deployed MLP
            — enables the Table-II silicon-equivalent throughput in
            stats() via `mapping.model_inference_cost`.  Derived
            automatically from a pure-MLP Deployment.
        silicon_cost: alternative to layer_sizes for non-MLP graphs —
            a precomputed `mapping.InferenceCost` (e.g.
            `convnet.cnn_inference_cost` for CNN deployments).
        mc_samples  : >0 routes this (silicon) model's requests through
            the per-request Monte-Carlo spec and serves the prediction
            of the summed votes; 0 serves one realization per request.
        warmup      : precompile the model's full bucket grid on every
            serving device now (otherwise call .warmup() before traffic).

        The model's dispatch spec is fixed here: every one of its
        micro-batches executes `pipe.run(x, spec[, keys])` with that one
        `InferenceSpec` — see `_Model.spec`.
        """
        if self._started:
            raise RuntimeError("register() before start()")
        if model_id in self._models:
            raise ValueError(f"model {model_id!r} already registered")
        if isinstance(model, (str, Path)):
            model = Deployment.load(model)
        if isinstance(model, Deployment):
            if layer_sizes is None and silicon_cost is None:
                layer_sizes = model.layer_sizes  # None for conv graphs
            pipe = model.pipeline()
        else:
            pipe = model
        silicon = pipe.physics is not None and not pipe.physics.is_noiseless
        if mc_samples and not silicon:
            raise ValueError("mc_samples needs a silicon-mode pipeline")
        if layer_sizes is not None and silicon_cost is not None:
            raise ValueError("pass layer_sizes OR silicon_cost, not both")
        cost = silicon_cost
        if layer_sizes is not None:
            if (int(layer_sizes[0]), int(layer_sizes[-1])) != \
                    (pipe.n_in, pipe.n_classes):
                raise ValueError(
                    f"layer_sizes {tuple(layer_sizes)} disagree with the "
                    f"pipeline ({pipe.n_in} -> {pipe.n_classes})"
                )
            plans = [
                mapping.plan_layer(int(n_out), int(n_in),
                                   pipe.head.bias_cells)
                for n_in, n_out in zip(layer_sizes[:-1], layer_sizes[1:])
            ]
            cost = mapping.model_inference_cost(
                plans, int(pipe.head.thresholds.shape[0])
            )
        if silicon:
            spec = (InferenceSpec(noise="per_request",
                                  mc_samples=int(mc_samples),
                                  reduction="sum")
                    if mc_samples else InferenceSpec(noise="per_request"))
        else:
            spec = InferenceSpec()
        self._models[model_id] = _Model(
            model_id=model_id, pipe=pipe, silicon=silicon,
            spec=spec, silicon_cost=cost,
        )
        if warmup:
            self._warm_model(self._models[model_id])

    def _warm_model(self, m: _Model) -> dict:
        # warm exactly the spec dispatch uses — every extra spec is
        # another XLA compile per bucket per device before traffic —
        # and with the SAME placement dispatch will stage with: jit
        # caches key on input sharding, so warming with a different
        # placement would never be hit and traffic would compile anyway
        times: dict = {}
        if self.fanout == "spmd":
            times.update(m.pipe.warmup(self.policy.max_batch,
                                       specs=(m.spec,),
                                       device=self._batch_sharding))
            return times
        for dev in self.devices:
            for (spec, bucket), s in m.pipe.warmup(
                self.policy.max_batch, specs=(m.spec,), device=dev
            ).items():
                times[(spec, bucket)] = times.get((spec, bucket), 0.0) + s
        return times

    def warmup(self) -> dict[str, dict]:
        """Precompile every (model, bucket, device) program variant.

        Returns {model_id: {(spec, bucket): seconds}} — per-program
        compile-cost attribution for serving startup (summed across
        devices for round-robin fan-out).
        """
        return {mid: self._warm_model(m)
                for mid, m in self._models.items()}

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> "PicBnnServer":
        """Validate the registry and launch the dispatch/completion
        threads; idempotent.  Returns self (context-manager entry)."""
        if self._started:
            return self
        if not self._models:
            raise RuntimeError("no models registered")
        for m in self._models.values():
            if m.pipe.max_bucket is None:
                continue
            # compare the BUCKET a full batch needs, not max_batch itself:
            # a non-power-of-two cap would pass a direct comparison and
            # then fail every full dispatch
            need = next_bucket(self.policy.max_batch, m.pipe.min_bucket)
            if need > m.pipe.max_bucket:
                raise ValueError(
                    f"policy.max_batch {self.policy.max_batch} needs "
                    f"bucket {need} > {m.model_id!r}'s pipeline "
                    f"max_bucket {m.pipe.max_bucket}"
                )
        self._started = True
        obs.watch_gc()
        self._dispatch_t = threading.Thread(
            target=self._dispatch_loop, name="picbnn-dispatch", daemon=True
        )
        self._complete_t = threading.Thread(
            target=self._complete_loop, name="picbnn-complete", daemon=True
        )
        self._dispatch_t.start()
        self._complete_t.start()
        return self

    def close(self) -> None:
        """Drain admitted requests, complete in-flight batches, join."""
        if self._closed:
            return
        self._closed = True
        self._batcher.close()
        if self._started:
            self._dispatch_t.join()
            self._complete_t.join()
        else:
            # never started: fail anything queued so no handle hangs
            while True:
                got = self._batcher.next_batch(timeout=0)
                if got is None:
                    break
                self._fail_batch(got[0], got[1],
                                 RuntimeError("server closed before start"))

    def __enter__(self) -> "PicBnnServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def _admit(self, model_id: str, images, keys, single: bool,
               block: bool, timeout: Optional[float]):
        t_submit = time.perf_counter()
        m = self._models.get(model_id)
        if m is None:
            raise KeyError(f"unknown model {model_id!r}; registered: "
                           f"{sorted(self._models)}")
        if self._closed:
            raise RuntimeError("server is closed")
        x = np.asarray(images, np.float32)
        if single:
            x = x.reshape(1, -1) if x.ndim == 1 else x
        # reject bad shapes HERE: inside the dispatch thread they would
        # fail a whole coalesced batch of innocent neighbors
        if x.ndim != 2 or x.shape[1] != m.pipe.n_in:
            raise ValueError(
                f"expected image(s) [{'' if single else 'W, '}"
                f"{m.pipe.n_in}] for model {model_id!r}, got shape "
                f"{np.shape(images)}"
            )
        if m.silicon:
            if keys is None:
                raise ValueError(
                    f"model {model_id!r} is silicon-mode: each request "
                    "must carry its own PRNG key (key(s)=...)"
                )
            keys = np.asarray(keys, np.uint32)
            if single:
                keys = keys.reshape(1, -1) if keys.ndim == 1 else keys
            if keys.shape != (len(x), 2):
                raise ValueError(
                    f"keys must be raw uint32 [{len(x)}, 2] PRNG keys, "
                    f"got {keys.shape}"
                )
        elif keys is not None:
            raise ValueError(
                f"model {model_id!r} is noiseless: key(s)= not accepted"
            )
        with self._uid_lock:
            uid0 = self._uid
            self._uid += len(x)
        slab = _Slab(uid0, model_id, x, keys, t_submit)
        self._batcher.put(model_id, slab, size=slab.n, t_enqueue=t_submit,
                          block=block, timeout=timeout)
        return slab

    def submit(self, model_id: str, image, key=None, *,
               block: bool = True,
               timeout: Optional[float] = None) -> _Handle:
        """Enqueue one single-image request; returns a result handle.

        image : [n_in] in the ±1 domain (anything np.asarray-able).
        key   : per-request PRNG key (raw uint32 [2]) — REQUIRED for a
            silicon-mode model (it makes the served draw reproducible),
            rejected for a noiseless one.
        block/timeout : admission behavior when `max_queue` is bounded;
            block=False raises QueueFullError instead of waiting.
        """
        slab = self._admit(model_id, image, key, True, block, timeout)
        return _Handle(slab, self)

    def submit_many(self, model_id: str, images, keys=None, *,
                    block: bool = True,
                    timeout: Optional[float] = None) -> GroupHandle:
        """Enqueue a burst of single-image requests in one admission
        round; returns a GroupHandle over all of them.

        Each image is still an independent request (own uid, own key,
        free to be coalesced with other traffic and split across
        micro-batches) — but the burst is admitted, queued, and
        dispatched as ONE contiguous slab, so the per-request Python
        cost that caps a GIL-bound serving loop is paid per burst (a
        real RPC front door receives framed bursts anyway).
        `images`: [W, n_in]; `keys`: [W, 2] for silicon models.
        """
        slab = self._admit(model_id, images, keys, False, block, timeout)
        return GroupHandle(slab, self)

    def _fail_batch(self, model_id: str, spans, err: BaseException) -> None:
        n = sum(s.n for s in spans)
        batch = _Batch(model_id, n, 0, -1, time.perf_counter(),
                       np.full(n, time.perf_counter()))
        batch.error = err
        pos = 0
        for s in spans:
            s.lot.spans.append((batch, s.lo, pos, s.n))
            s.lot.placed += s.n
            pos += s.n
        with self._dispatch_cv:
            self._dispatch_cv.notify_all()
        batch.t_done = time.perf_counter()
        batch.event.set()
        with self._records_lock:
            self._records.append(batch)

    def _dispatch_loop(self) -> None:
        while True:
            got = self._batcher.next_batch()
            if got is None:
                break
            model_id, spans = got
            try:
                self._dispatch(self._models[model_id], spans)
            except BaseException as e:  # resolve, don't hang clients
                self._fail_batch(model_id, spans, e)
        with self._inflight_cond:
            self._inflight.append(None)  # completion sentinel
            self._inflight_cond.notify_all()

    def _dispatch(self, m: _Model, spans) -> None:
        t_dispatch = time.perf_counter()
        bid = next(self._batch_ids)
        n = sum(s.n for s in spans)
        pipe = m.pipe
        bucket = next_bucket(n, pipe.min_bucket, pipe.max_bucket)
        # assemble straight into a bucket-sized host buffer with one
        # vectorized copy per span: every dispatch then presents the
        # exact operand shapes warmup() compiled for (a ragged [n, ...]
        # staging array would re-specialize the program per distinct n —
        # a fresh compile mid-traffic); pad rows are zeros (valid
        # ±1-domain garbage, dropped at readback)
        with obs.span("serve.assemble", batch=bid):
            x = np.zeros((bucket, pipe.n_in), np.float32)
            keys = np.zeros((bucket, 2), np.uint32) if m.silicon else None
            t_subs = np.empty(n)
            placed = []
            pos = 0
            for s in spans:
                k, slab = s.n, s.lot
                x[pos:pos + k] = slab.x[s.lo:s.hi]
                if m.silicon:
                    keys[pos:pos + k] = slab.keys[s.lo:s.hi]
                t_subs[pos:pos + k] = slab.t_submit
                placed.append((slab, s.lo, pos, k))
                pos += k
        if self.fanout == "spmd":
            dev_idx = -1
            target = self._batch_sharding
        else:
            dev_idx = self._next_dev
            self._next_dev = (self._next_dev + 1) % len(self.devices)
            target = self.devices[dev_idx]
        # the logical rows are counted, not the bucket's padded ones
        xd = obs.stage(x, target, rows=n, batch=bid)
        kd = obs.stage(keys, target, rows=0, batch=bid) if m.silicon else None
        with obs.span("serve.enqueue", batch=bid):
            votes = pipe.run(xd, m.spec, keys=kd)
        # jax dispatch is async: `votes` is a device future; hand it to
        # the completion thread and go assemble/stage the next batch
        batch = _Batch(m.model_id, n, bucket, dev_idx, t_dispatch, t_subs,
                       bid)
        for slab, lo, bpos, k in placed:
            slab.spans.append((batch, lo, bpos, k))
            slab.placed += k
        with self._dispatch_cv:
            self._dispatch_cv.notify_all()
        with self._inflight_cond, obs.span("serve.backpressure", batch=bid):
            while len(self._inflight) >= self.policy.max_inflight:
                self._inflight_cond.wait()
            self._inflight.append((batch, votes))
            self._inflight_cond.notify_all()

    def _complete_loop(self) -> None:
        while True:
            with self._inflight_cond:
                while not self._inflight:
                    self._inflight_cond.wait()
                item = self._inflight.pop(0)
                self._inflight_cond.notify_all()
            if item is None:
                break
            batch, votes = item
            try:
                with obs.span("serve.readback", batch=batch.bid):
                    votes_np = np.asarray(votes)[:batch.n]  # sync + drop pad
                batch.votes = votes_np
                batch.preds = votes_np.argmax(-1)
            except BaseException as e:
                batch.error = e
            batch.t_done = time.perf_counter()
            batch.event.set()
            with self._records_lock:
                self._records.append(batch)
                if batch.error is None:
                    tot = self._totals.setdefault(
                        batch.model_id,
                        [0, 0, batch.t_dispatch, batch.t_done],
                    )
                    tot[0] += batch.n
                    tot[1] += 1
                    tot[2] = min(tot[2], batch.t_dispatch)
                    tot[3] = max(tot[3], batch.t_done)

    # ------------------------------------------------------------------
    # metrics
    # ------------------------------------------------------------------
    def stats(self) -> ServerStats:
        """Aggregate ServerStats: lifetime-accurate counts/throughput
        (running totals), percentiles over the last `stats_window`
        completed batches."""
        with self._records_lock:
            records = [b for b in self._records if b.error is None]
            totals = {k: list(v) for k, v in self._totals.items()}
        if not totals:
            empty = latency_summary([])
            return ServerStats(0, 0, 0.0, 0.0, 0.0, 0.0,
                               self._batcher.high_water, empty, empty,
                               empty, {})

        def _summaries(rs):
            if not rs:
                e = latency_summary([])
                return e, e, e, 0.0
            lat = np.concatenate([b.t_done - b.t_submits for b in rs])
            que = np.concatenate([b.t_dispatch - b.t_submits for b in rs])
            svc = np.concatenate(
                [np.full(b.n, b.t_done - b.t_dispatch) for b in rs]
            )
            occ = float(np.mean([b.n / b.bucket for b in rs]))
            return (latency_summary(lat * 1e3), latency_summary(que * 1e3),
                    latency_summary(svc * 1e3), occ)

        n_req = sum(t[0] for t in totals.values())
        n_batches = sum(t[1] for t in totals.values())
        wall = (max(t[3] for t in totals.values())
                - min(t[2] for t in totals.values()))
        lat, que, svc, occ = _summaries(records)
        per_model = {}
        for mid, tot in totals.items():
            m = self._models[mid]
            mlat, mque, msvc, mocc = _summaries(
                [b for b in records if b.model_id == mid]
            )
            mwall = tot[3] - tot[2]
            si = (m.silicon_cost.inferences_per_s
                  if m.silicon_cost else None)
            rate = tot[0] / mwall if mwall > 0 else float("inf")
            per_model[mid] = ModelStats(
                model_id=mid,
                n_requests=tot[0],
                n_batches=tot[1],
                mean_batch=tot[0] / tot[1],
                mean_occupancy=mocc,
                inf_per_s=rate,
                latency=mlat,
                queue=mque,
                service=msvc,
                silicon_inf_per_s=si,
                vs_silicon=(rate / si if si else None),
            )
        return ServerStats(
            n_requests=n_req,
            n_batches=n_batches,
            wall_s=wall,
            inf_per_s=n_req / wall if wall > 0 else float("inf"),
            mean_batch=n_req / n_batches,
            mean_occupancy=occ,
            queue_high_water=self._batcher.high_water,
            latency=lat,
            queue=que,
            service=svc,
            per_model=per_model,
        )
