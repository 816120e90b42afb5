"""End-to-end deployed-BNN inference pipeline (packed domain, fused).

`compile_pipeline(folded, ens_cfg)` turns a folded binary MLP (list of
`bnn.FoldedLayer`) plus an Algorithm-1 ensemble config into a jitted
batch classifier driven by a declarative request spec
(`repro.spec.InferenceSpec`):

    pipe = compile_pipeline(folded, EnsembleConfig())
    votes = pipe.run(x_pm1, InferenceSpec())              # [B, C] int32
    pred  = pipe.run(x_pm1, InferenceSpec(reduction="argmax"))  # [B]

`run(x, spec, key=..., keys=...)` is the ONE entry point: it compiles
and caches exactly one fused program per distinct spec, and centralizes
the batch bucketing, pad/trim, and PRNG-key shape logic that the legacy
eight-method family (`votes`, `votes_each`, `votes_mc`, `votes_mc_each`,
`votes_mc_each_sum`, `cum_votes`, `predict`, `predict_each`) used to
copy-paste.  Those methods remain as thin deprecated shims over `run()`
for one release — bit-exact equal by construction (each shim just names
a spec) and proven so by the pre-redesign oracle tests.

Semantics are bit-exact equal to the digital oracle
(`bnn.folded_forward_exact` hidden layers + `ensemble.votes_fused` head);
tests/test_pipeline.py asserts this across bank configurations.

Silicon mode: `compile_pipeline(folded, cfg, noise=SILICON)` threads the
unified device physics (`core/physics.SearchPhysics`) through the SAME
fused program — per-pass effective thresholds are sampled as [P, B, C]
float arrays (sigma_hd per row; sigma_vref / sigma_tjitter pass-global
through the Table-I knob schedule; temp_drift_hd systematic) and only the
head compare changes, so the HD-once/compare-33x amortization survives
noise.  The spec's `noise` axis selects the draw shape:

  "batch"       — one realization for the whole batch (`key=`); row
                  realizations depend on batch composition and bucket
                  padding (a measurement-style draw).
  "per_request" — one batch_shape=() draw per row from `keys[i]`;
                  results are invariant to how a serving loop coalesces
                  requests (the serve determinism contract).

`mc_samples=S` vmaps S independent threshold realizations over ONE
Hamming-distance computation; `cumulative=True` exposes the per-pass
cumulative votes [P, B, C] that noisy Fig.-5-style truncated sweeps need
(`ensemble.sweep_from_votes` is noiseless-only — see its docstring).
`InferenceSpec(noise="off", cumulative=True)` is the exact noiseless
staircase, valid on ANY pipeline — the explicit form of what `cum_votes`
used to do by silently substituting `PRNGKey(0)`.  With
`noise=NOISELESS` every noisy spec is bit-identical to the noiseless
oracle (tested).

Two fused implementations, selected by `impl` (default: by backend):

  pallas — kernels/fused_mlp.py: one kernel launch per batch block,
           hidden activations resident in VMEM (the TPU deployment path).
           The backend alone decides how it runs: compiled by Mosaic on
           TPU, through the Pallas interpreter elsewhere (semantics
           tests only).  The noisy path feeds the kernel a precomputed
           [P, B, C] threshold-sample operand — randomness never enters
           the kernel.
  xla    — the same packed-domain math as a single jitted XLA program:
           activations stay uint32-packed between layers and the whole
           net fuses into one executable (the portable fast path — on
           CPU this is what beats the layer-by-layer unpacked flow).  The
           noisy path broadcasts the sampled [P, B, C] thresholds against
           the one HD computation.

Monte-Carlo, cumulative, and per-request specs always use the XLA-twin
math (per-pass/per-sample outputs do not fit the kernel's single [B, C]
result block); the twins are bit-exact equal so this is a pure
scheduling choice.

Convolutional graphs: `folded` may start with a prefix of
`convnet.FoldedConvLayer` (a deployed end-to-end-binary CNN, e.g.
`convnet.fold_cnn` output).  The pipeline then takes RAW [0,1] pixels
[B, side*side]: the binary input layer (`image_encoding`, thermometer by
default) and the channel packing run inside the jitted `_pack_fn`, the
conv stack executes in the packed domain (`kernels/fused_conv.py` on the
pallas path, the same shared math as one XLA program otherwise), and the
flatten feeds the FC stage — so every spec works identically for conv
and MLP deployments.  Bit-exactness bar: the unpacked oracle
`kernels.ref.conv_votes_ref` (tests/test_conv.py).

Batch-size bucketing: inputs are zero-padded up to the next bucket
(powers of two, floor `min_bucket`) so a serving loop with ragged batch
sizes compiles O(log B) program variants instead of one per size.

Persistable deployments (`repro.deploy.Deployment`) bundle the folded
layers + encoding + configs this function takes, and rebuild the same
pipeline from disk — see deploy.py.

Observability (`repro.obs`): each `run` is a `picbnn.run` profiler span
with the pipeline's call number (`call=`), enclosing `picbnn.stage`
(host->device copy, counted in `stage.*`), `picbnn.pack`, `picbnn.pad`
(padded batches only) and `picbnn.vote`, each the dispatch of one
program.  The programs carry fixed names: `picbnn_pack`, and one
`InferenceSpec.program_name` per spec (`picbnn_votes_off`, ...), so a
device trace names them `jit_picbnn_*`.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
import warnings
from typing import Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import binarize
from repro.core.bnn import FoldedLayer
from repro.core.convnet import FoldedConvLayer
from repro.core.device_model import NoiseModel
from repro.core.ensemble import CAMEnsembleHead, EnsembleConfig, build_head
from repro.core.physics import SearchPhysics
from repro.kernels import fused_conv, fused_mlp
from repro.spec import InferenceSpec, legacy_entry_spec


def next_bucket(n: int, min_bucket: int = 64,
                max_bucket: Optional[int] = None) -> int:
    """Smallest power-of-two bucket >= n (floored at min_bucket).

    n == 0 is rejected (an empty batch has no bucket — dispatching it
    would burn a full min_bucket of padded compute for zero results), as
    is exceeding the explicit `max_bucket` cap: a serving loop sets the
    cap to its max batch so the compiled-variant set is closed (warmup
    covers every bucket) and an oversized dispatch fails loudly instead
    of silently compiling a new program variant mid-traffic.
    """
    if n <= 0:
        raise ValueError(f"batch size must be >= 1, got {n}")
    b = min_bucket
    while b < n:
        b *= 2
    if max_bucket is not None and b > max_bucket:
        raise ValueError(
            f"batch {n} needs bucket {b} > max_bucket {max_bucket}; "
            "split the batch or recompile with a larger cap"
        )
    return b


def bucket_grid(max_batch: int, min_bucket: int = 64) -> tuple[int, ...]:
    """Every bucket a batch in 1..max_batch can land on (ascending)."""
    out = [min_bucket]
    while out[-1] < max_batch:
        out.append(out[-1] * 2)
    return tuple(out)


def _named(fn: Callable, name: str) -> Callable:
    """`fn` under the name `jax.jit` gives its program (`jit_<name>`)."""
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return named


def _head_hd_xla(x_packed, layer_ws, layer_cs, layer_n_bits, head_rows,
                 bias_cells: int):
    """Packed-domain fused forward up to the head Hamming distances.

    Same math as the Pallas kernel: XNOR-popcount matvec + C + sign +
    repack per hidden layer, then HD of the (bias-appended) head query
    against every class row.  Returns [B, C] int32 — the one quantity
    every vote path (noiseless, noisy, Monte-Carlo, cumulative) compares
    thresholds against.
    """
    q = x_packed
    n_layers = len(layer_ws)
    for i, (w, c, n_bits) in enumerate(zip(layer_ws, layer_cs, layer_n_bits)):
        hd = binarize.hamming_packed(q[:, None, :], w)
        y = (n_bits - 2 * hd) + c[None, :]
        bits = (y >= 0).astype(jnp.uint8)
        if i + 1 == n_layers:  # head query: append bias drive bits
            ones = jnp.ones((bits.shape[0], bias_cells), jnp.uint8)
            bits = jnp.concatenate([bits, ones], axis=-1)
        q = binarize.pack_bits(bits)
    return binarize.hamming_packed(q[:, None, :], head_rows)


_LEGACY_WARNED: set = set()


def _warn_legacy(name: str) -> None:
    """One DeprecationWarning per legacy entry point per process."""
    if name in _LEGACY_WARNED:
        return
    _LEGACY_WARNED.add(name)
    warnings.warn(
        f"CompiledPipeline.{name}() is a deprecated shim over "
        f"run(x, InferenceSpec(...)) — see repro.spec.legacy_entry_spec "
        "and the README migration table; it will be removed next release",
        DeprecationWarning,
        stacklevel=3,
    )


@dataclasses.dataclass
class CompiledPipeline:
    """A jitted end-to-end batch classifier for one deployed BNN.

    The execution surface is `run(x, spec)` / `run_packed(x_packed,
    spec)`: one fused XLA program is compiled and cached per distinct
    `InferenceSpec` (`program(spec)` is the cache), and all bucketing /
    padding / result trimming / PRNG-key validation lives in `run_packed`
    — once, for every spec.  The legacy method family survives as
    deprecated shims that name their spec.
    """

    head: CAMEnsembleHead
    n_in: int
    n_classes: int
    impl: str
    min_bucket: int
    head_only: bool  # no hidden layers: input feeds the CAM head directly
    physics: Optional[SearchPhysics]  # None <=> compiled without noise=
    _program_factory: Callable  # InferenceSpec -> jitted program
    _pack_fn: Callable  # jitted ±1 [B, n_in] -> packed
    max_bucket: Optional[int] = None  # serving cap on the bucket grid
    _programs: dict = dataclasses.field(default_factory=dict, repr=False)
    _calls: Iterator[int] = dataclasses.field(
        default_factory=itertools.count, repr=False)  # span `call=` ids

    # ------------------------------------------------------------------
    # the generic compiled-request API
    # ------------------------------------------------------------------
    def program(self, spec: InferenceSpec) -> Callable:
        """The compiled program for `spec` (built and cached on first use).

        Signature depends on the spec's noise axis: `f(x_packed)` for
        "off", `f(x_packed, key)` for "batch", `f(x_packed, keys)` for
        "per_request" — `run_packed` dispatches accordingly.  Callers
        normally never touch this; it exists so warmup and tests can
        assert cache identity.
        """
        prog = self._programs.get(spec)
        if prog is None:
            if spec.needs_physics and self.physics is None:
                raise ValueError(
                    f"{spec.describe()} needs a silicon-mode pipeline: "
                    "recompile with compile_pipeline(..., noise=<NoiseModel>)"
                )
            prog = self._program_factory(spec)
            self._programs[spec] = prog
        return prog

    def run(self, x: jax.Array, spec: InferenceSpec, *,
            key: Optional[jax.Array] = None,
            keys: Optional[jax.Array] = None) -> jax.Array:
        """Execute one declarative inference request on a raw batch.

        x    : [B, n_in] — ±1 activations for MLP pipelines, RAW [0,1]
               pixels for conv pipelines (the binary input encoding and
               channel packing run inside the jitted pack step).
        spec : what to run (`repro.spec.InferenceSpec`).
        key  : batch-level PRNG key — required iff spec.noise=="batch".
        keys : per-request raw uint32 [B, 2] PRNG keys — required iff
               spec.noise=="per_request".

        Returns int32 votes/predictions shaped per the spec (see
        repro/spec.py's shape table), trimmed to the logical batch.
        """
        call = next(self._calls)
        with obs.span("picbnn.run", call=call):
            return self.run_packed(self._pack_input(x, call), spec, key=key,
                                   keys=keys, call=call)

    def run_packed(self, x_packed: jax.Array, spec: InferenceSpec, *,
                   key: Optional[jax.Array] = None,
                   keys: Optional[jax.Array] = None,
                   call: Optional[int] = None) -> jax.Array:
        """`run` for an already-packed input batch [B, Kw0].

        Conv pipelines: Kw0 = side*side*Cw0, the row-flattened channel-
        packed encoded image the jitted pack step emits (`_pack_input`).
        This is the ONE place bucket padding, key-shape validation, and
        result trimming happen, for every spec.  `call` is the span id
        of the `run` this continues; None opens a `picbnn.run` of its own.
        """
        if call is None:
            call = next(self._calls)
            with obs.span("picbnn.run", call=call):
                return self.run_packed(x_packed, spec, key=key, keys=keys,
                                       call=call)
        prog = self.program(spec)  # physics capability check happens here
        x_packed, b = self._bucketed(x_packed, call)
        if spec.needs_keys:
            if key is not None:
                raise ValueError(
                    f"{spec.describe()} takes per-request keys=, not a "
                    "batch-level key="
                )
            if keys is None:
                raise ValueError(
                    f"{spec.describe()} needs per-request keys= "
                    "([B, 2] raw uint32 PRNG keys)"
                )
            keys = self._each_keys(keys, b, x_packed.shape[0], call)
            with obs.span("picbnn.vote", call=call):
                out = prog(x_packed, keys)
        elif spec.needs_key:
            if keys is not None:
                raise ValueError(
                    f"{spec.describe()} takes one batch-level key=, not "
                    "per-request keys="
                )
            if key is None:
                raise ValueError(
                    f"{spec.describe()} needs an explicit key= (each call "
                    "is one silicon realization)"
                )
            with obs.span("picbnn.vote", call=call):
                out = prog(x_packed, key)
        else:
            if key is not None or keys is not None:
                raise ValueError(
                    f'{spec.describe()} is deterministic (noise="off"): '
                    "it accepts neither key= nor keys="
                )
            with obs.span("picbnn.vote", call=call):
                out = prog(x_packed)
        return self._trim(out, b, spec.batch_axis)

    # ------------------------------------------------------------------
    # shared glue (bucketing / packing / trimming / key shapes)
    # ------------------------------------------------------------------
    def _pack_input(self, x_pm1: jax.Array, call: int) -> jax.Array:
        # one jitted dispatch: the eager op-by-op pack costs ~5x the whole
        # fused vote program in host dispatch overhead (serving hot path)
        x = obs.stage(x_pm1, call=call)
        with obs.span("picbnn.pack", call=call):
            return self._pack_fn(x)

    def _bucketed(self, x_packed: jax.Array, call: int):
        b = x_packed.shape[0]
        bp = next_bucket(b, self.min_bucket, self.max_bucket)
        if bp != b:
            with obs.span("picbnn.pad", call=call):
                x_packed = jnp.pad(x_packed, ((0, bp - b), (0, 0)))
        return x_packed, b

    @staticmethod
    def _trim(out: jax.Array, b: int, axis: int) -> jax.Array:
        # slicing is an eager XLA op per call — skip it when the batch
        # already fills its bucket (the serving hot path by construction)
        if out.shape[axis] == b:
            return out
        return out[:b] if axis == 0 else out[:, :b]

    def _each_keys(self, keys, b: int, bp: int, call: int) -> jax.Array:
        keys = obs.stage(keys, rows=0, call=call)
        if keys.ndim != 2 or keys.shape[0] != b:
            raise ValueError(
                f"keys must be [B, key_width] raw uint32 PRNG keys with "
                f"B == batch ({b}), got shape {tuple(keys.shape)} — stack "
                "jax.random.PRNGKey / jax.random.split outputs"
            )
        if bp != b:  # pad rows get (valid) zero keys; results are sliced
            with obs.span("picbnn.pad", call=call):
                keys = jnp.pad(keys, ((0, bp - b), (0, 0)))
        return keys

    def buckets_for(self, max_batch: int) -> tuple[int, ...]:
        """The bucket grid batches 1..max_batch dispatch into."""
        return bucket_grid(max_batch, self.min_bucket)

    # ------------------------------------------------------------------
    # spec-driven warmup
    # ------------------------------------------------------------------
    #: legacy entry names accepted by warmup(entries=) (deprecated —
    #: pass specs= instead; see repro.spec.legacy_entry_spec)
    WARMUP_ENTRIES = ("votes", "votes_noisy", "votes_each", "votes_mc",
                      "votes_mc_each", "votes_mc_each_sum")

    def default_warmup_specs(
        self, mc_samples: Optional[int] = None
    ) -> tuple[InferenceSpec, ...]:
        """Every spec this pipeline supports out of the box.

        Noiseless pipelines warm the plain vote program; silicon-mode
        pipelines add the batch-draw and per-request programs, plus the
        Monte-Carlo family when `mc_samples` is given.  A serving loop
        should instead pass exactly its dispatch spec(s) — each spec is
        a separate XLA compile per bucket.
        """
        if self.physics is None:
            return (InferenceSpec(),)
        specs = [
            InferenceSpec(),
            InferenceSpec(noise="batch"),
            InferenceSpec(noise="per_request"),
        ]
        if mc_samples:
            specs += [
                InferenceSpec(noise="batch", mc_samples=mc_samples),
                InferenceSpec(noise="per_request", mc_samples=mc_samples),
                InferenceSpec(noise="per_request", mc_samples=mc_samples,
                              reduction="sum"),
            ]
        return tuple(specs)

    def warmup(self, max_batch: int, *,
               specs: Optional[Sequence[InferenceSpec]] = None,
               key: Optional[jax.Array] = None,
               mc_samples: Optional[int] = None, device=None,
               entries: Optional[Sequence[str]] = None
               ) -> dict[tuple[InferenceSpec, int], float]:
        """Precompile every (spec, bucket) program a serving loop needs.

        Runs one dummy batch per (spec, bucket) pair and blocks until
        ready, so first-request compile latency never shows up in served
        percentiles.

        specs   : the request specs to warm; default
            `default_warmup_specs(mc_samples)`.  A serving loop passes
            exactly its dispatch spec(s) — startup time is
            specs x buckets x devices XLA compiles.
        entries : DEPRECATED legacy entry names (translated through
            `repro.spec.legacy_entry_spec`); mutually exclusive with
            specs.
        device  : commits the dummy operands — a device for round-robin
            fan-out, or a `jax.sharding.Sharding` for SPMD fan-out (jit
            caches key on input sharding, so warming with a different
            placement than dispatch would never hit).  Scalar keys are
            replicated when a sharding is given (a [2] key cannot take a
            batch-axis shard).

        Returns {(spec, bucket): seconds} — per-program attribution, so
        serving startup can report exactly where compile time went;
        dominated by compile time on first call, ~free when the program
        cache already holds the (spec, bucket) variant.
        """
        if entries is not None:
            if specs is not None:
                raise ValueError("pass specs= or legacy entries=, not both")
            _warn_legacy("warmup(entries=)")
            unknown = set(entries) - set(self.WARMUP_ENTRIES)
            if unknown:
                raise ValueError(f"unknown warmup entries {sorted(unknown)}")
            specs = tuple(
                legacy_entry_spec(
                    e, mc_samples if e.startswith("votes_mc") else None
                )
                for e in entries
            )
        if specs is None:
            specs = self.default_warmup_specs(mc_samples)
        for spec in specs:  # capability check before any compile work
            if spec.needs_physics and self.physics is None:
                raise ValueError(
                    f"warmup of {spec.describe()} needs a silicon-mode "
                    "pipeline: recompile with compile_pipeline(..., "
                    "noise=<NoiseModel>)"
                )

        replicated = None
        if isinstance(device, jax.sharding.NamedSharding):
            from jax.sharding import PartitionSpec

            replicated = jax.sharding.NamedSharding(device.mesh,
                                                    PartitionSpec())
        times: dict[tuple[InferenceSpec, int], float] = {}
        for b in self.buckets_for(max_batch):
            x = jnp.ones((b, self.n_in), jnp.float32)
            k = key if key is not None else jax.random.PRNGKey(0)
            ks = jax.random.split(k, b)
            if device is not None:
                x = jax.device_put(x, device)
                k = jax.device_put(k, replicated or device)
                ks = jax.device_put(ks, device)  # batch-sharded like x
            for spec in specs:
                t0 = time.perf_counter()
                jax.block_until_ready(self.run(
                    x, spec,
                    key=k if spec.needs_key else None,
                    keys=ks if spec.needs_keys else None,
                ))
                times[(spec, b)] = time.perf_counter() - t0
        return times

    # ------------------------------------------------------------------
    # DEPRECATED legacy entry points — thin shims over run()
    # ------------------------------------------------------------------
    def votes(self, x_pm1: jax.Array, key: Optional[jax.Array] = None):
        """DEPRECATED shim: `run(x, InferenceSpec())`, or with `key` one
        batch-level silicon draw (`InferenceSpec(noise="batch")`).

        Input domain: ±1 activations for MLP pipelines; RAW [0,1] pixels
        for conv pipelines (n_in = image_side**2 — the binary input
        encoding and channel packing run inside the jitted pack step).
        With the NOISELESS model the keyed path is bit-identical to the
        noiseless one.
        """
        _warn_legacy("votes")
        if key is None:
            return self.run(x_pm1, InferenceSpec())
        return self.run(x_pm1, InferenceSpec(noise="batch"), key=key)

    def votes_packed(self, x_packed: jax.Array,
                     key: Optional[jax.Array] = None) -> jax.Array:
        """DEPRECATED shim: `run_packed` with the `votes` specs."""
        _warn_legacy("votes_packed")
        if key is None:
            return self.run_packed(x_packed, InferenceSpec())
        return self.run_packed(x_packed, InferenceSpec(noise="batch"),
                               key=key)

    def votes_mc(self, x_pm1: jax.Array, key: jax.Array,
                 n_samples: int) -> jax.Array:
        """DEPRECATED shim: `InferenceSpec(noise="batch", mc_samples=S)`
        -> [S, B, C] Monte-Carlo silicon votes (HD computed ONCE)."""
        _warn_legacy("votes_mc")
        return self.run(
            x_pm1,
            InferenceSpec(noise="batch", mc_samples=int(n_samples)),
            key=key,
        )

    def votes_each(self, x_pm1: jax.Array, keys: jax.Array) -> jax.Array:
        """DEPRECATED shim: `InferenceSpec(noise="per_request")` — one
        batch_shape=() draw per row; invariant to batch composition (the
        serving determinism contract; see repro/spec.py)."""
        _warn_legacy("votes_each")
        return self.run(x_pm1, InferenceSpec(noise="per_request"),
                        keys=keys)

    def votes_mc_each(self, x_pm1: jax.Array, keys: jax.Array,
                      n_samples: int) -> jax.Array:
        """DEPRECATED shim: `InferenceSpec(noise="per_request",
        mc_samples=S)` -> [S, B, C]; sample s of request i is drawn from
        split(keys[i], S)[s], so results are batching-invariant."""
        _warn_legacy("votes_mc_each")
        return self.run(
            x_pm1,
            InferenceSpec(noise="per_request", mc_samples=int(n_samples)),
            keys=keys,
        )

    def votes_mc_each_sum(self, x_pm1: jax.Array, keys: jax.Array,
                          n_samples: int) -> jax.Array:
        """DEPRECATED shim: the per-request MC spec with
        reduction="sum" — the MC serving aggregate, reduction fused into
        the compiled program."""
        _warn_legacy("votes_mc_each_sum")
        return self.run(
            x_pm1,
            InferenceSpec(noise="per_request", mc_samples=int(n_samples),
                          reduction="sum"),
            keys=keys,
        )

    def predict_each(self, x_pm1: jax.Array, keys: jax.Array) -> jax.Array:
        """DEPRECATED shim: `InferenceSpec(noise="per_request",
        reduction="argmax")` — per-request-key Algorithm 1 prediction."""
        _warn_legacy("predict_each")
        return self.run(
            x_pm1,
            InferenceSpec(noise="per_request", reduction="argmax"),
            keys=keys,
        )

    def cum_votes(self, x_pm1: jax.Array,
                  key: Optional[jax.Array] = None) -> jax.Array:
        """DEPRECATED shim: per-pass cumulative votes [P, B, C].

        key given  -> `InferenceSpec(noise="batch", cumulative=True)`:
            one silicon realization's staircase (the silicon-conditioned
            replacement for `ensemble.sweep_from_votes`, which is valid
            ONLY noiseless).
        key=None   -> `InferenceSpec(cumulative=True)`: the exact
            noiseless staircase (== sweep_from_votes of the fused
            total).  This used to silently substitute `PRNGKey(0)`; it
            is now an explicit deterministic spec, valid on any
            pipeline.  A noise-compiled pipeline must still be given a
            key explicitly — each call is one silicon realization.
        """
        _warn_legacy("cum_votes")
        if key is None:
            if self.physics is not None and not self.physics.is_noiseless:
                raise ValueError(
                    "cum_votes on a noise-compiled pipeline needs an "
                    "explicit key (each call is one silicon realization); "
                    "for the deterministic staircase run the explicit "
                    'spec InferenceSpec(noise="off", cumulative=True) on '
                    "a noiseless pipeline"
                )
            return self.run(x_pm1, InferenceSpec(cumulative=True))
        return self.run(x_pm1, InferenceSpec(noise="batch", cumulative=True),
                        key=key)

    def predict(self, x_pm1: jax.Array,
                key: Optional[jax.Array] = None) -> jax.Array:
        """DEPRECATED shim: `InferenceSpec(reduction="argmax")` —
        Algorithm 1 prediction (per-class majority vote -> argmax)."""
        _warn_legacy("predict")
        if key is None:
            return self.run(x_pm1, InferenceSpec(reduction="argmax"))
        return self.run(
            x_pm1, InferenceSpec(noise="batch", reduction="argmax"), key=key
        )

    def __call__(self, x_pm1: jax.Array,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Sugar for the deprecated `predict` shim."""
        return self.predict(x_pm1, key)


def compile_pipeline(
    folded: Sequence,
    ens_cfg: EnsembleConfig | None = None,
    *,
    impl: str | None = None,
    bq: int | None = None,
    min_bucket: int = 64,
    max_bucket: int | None = None,
    noise: NoiseModel | None = None,
    params=None,
    donate: bool = False,
    image_side: int | None = None,
    image_encoding: binarize.InputEncoding | None = None,
) -> CompiledPipeline:
    """Compile a folded BNN + ensemble head into a fused batch classifier.

    folded  : `bnn.fold` output — hidden layers + the output layer (last).
              May start with a prefix of `convnet.FoldedConvLayer`
              (`convnet.fold_cnn` output): the pipeline then runs the
              end-to-end-binary CNN workload and its input domain becomes
              RAW [0,1] pixels [B, image_side**2] (the binary input
              encoding runs inside the jitted pack step).
    ens_cfg : Algorithm-1 config (thresholds / bias cells); default paper's.
    impl    : "pallas" | "xla" | None (auto: pallas on TPU, xla elsewhere).
              The backend alone decides how the kernel runs: compiled by
              Mosaic on TPU, through the Pallas interpreter elsewhere
              (semantics tests, not speed).
    bq      : Pallas batch rows per kernel block; default 128, one vreg
              of lanes (the batch is the kernels' lane axis — DESIGN.md
              §4 and §10 derive the VMEM budgets).  Compiled blocks need
              a multiple of 128; interpret mode takes any size.
    noise   : optional NoiseModel — enables the silicon-mode specs
              (noise="batch"/"per_request", Monte-Carlo, noisy
              cumulative) by building a SearchPhysics bundle from the
              head's threshold schedule; `params` optionally overrides
              the AnalogParams.  noise=None keeps the pipeline
              noiseless-only (no knob-schedule work at compile time).
    max_bucket : optional cap on the batch-bucket grid (see next_bucket);
              serving loops set it to their max batch so warmup() closes
              the compiled-variant set.
    donate  : donate the packed input buffer to the compiled programs
              (donate_argnums) — the packing step produces a fresh
              buffer per call, so a serving loop can hand it to the
              program and save an allocation on TPU/GPU.  No effect on
              results; backends that can't reuse the buffer (CPU) just
              ignore the donation.  Off by default because `run_packed`
              is public API and donation invalidates the caller's array.
    image_side : REQUIRED for conv graphs — square input image side
              (`n_in = image_side**2` raw pixels).  Rejected for pure
              MLP graphs.
    image_encoding : the binary input layer for conv graphs
              (`binarize.InputEncoding`); its width must equal the first
              conv layer's c_in.  Default: thermometer of that width.

    The returned pipeline compiles lazily: `run(x, spec)` builds one
    fused program per distinct `InferenceSpec` on first use (warmup()
    precompiles a chosen set).  `repro.deploy.deploy(...)` wraps this
    call in a persistable `Deployment` artifact.
    """
    ens_cfg = ens_cfg or EnsembleConfig()
    if len(folded) < 1:
        raise ValueError("need at least the output layer")
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown pipeline impl {impl!r}")
    interpret = jax.default_backend() != "tpu"

    rest = list(folded)
    conv_layers: list[FoldedConvLayer] = []
    while rest and isinstance(rest[0], FoldedConvLayer):
        conv_layers.append(rest.pop(0))
    if any(isinstance(l, FoldedConvLayer) for l in rest):
        raise ValueError("conv layers must form a prefix of `folded`")
    if not rest:
        raise ValueError("need an output FC layer after the conv stack")
    if conv_layers and image_side is None:
        raise ValueError("conv graphs need image_side=")
    if not conv_layers and (image_side is not None
                            or image_encoding is not None):
        raise ValueError("image_side/image_encoding are conv-only options")

    hidden, out_layer = list(rest[:-1]), rest[-1]
    head = build_head(out_layer, ens_cfg)
    n_classes = head.n_classes

    layer_ws = tuple(
        binarize.pack_bits(jnp.asarray((l.weights_pm1 > 0).astype(np.uint8)))
        for l in hidden
    )
    layer_cs = tuple(jnp.asarray(l.c, jnp.int32) for l in hidden)
    layer_n_bits = tuple(int(l.n_in) for l in hidden)
    head_rows = head.cam.rows_packed
    thresholds = head.thresholds

    conv_metas = conv_ws = conv_cs = None
    head_direct = False
    if conv_layers:
        enc = image_encoding or binarize.InputEncoding(
            "thermometer", conv_layers[0].c_in
        )
        if enc.width != conv_layers[0].c_in:
            raise ValueError(
                f"encoding width {enc.width} != first conv c_in "
                f"{conv_layers[0].c_in}"
            )
        conv_metas = fused_conv.conv_metas_for(conv_layers, image_side)
        conv_ws = tuple(fused_conv.pack_conv_rows(l) for l in conv_layers)
        conv_cs = tuple(jnp.asarray(l.c, jnp.int32) for l in conv_layers)
        mf = conv_metas[-1]
        n_pos, c_f = mf.out_side * mf.out_side, mf.c_out
        first_fc = hidden[0] if hidden else out_layer
        if int(first_fc.n_in) != n_pos * c_f:
            raise ValueError(
                f"first FC layer n_in {first_fc.n_in} != flattened conv "
                f"features {n_pos}*{c_f}"
            )
        head_direct = not hidden
        if head_direct and c_f % 32:
            raise ValueError(
                "conv -> head-direct needs last conv c_out % 32 == 0 "
                f"(word-aligned flatten), got {c_f}"
            )
        if hidden:
            # the flatten keeps per-position word padding — repack the
            # first FC layer's rows with the matching alignment
            layer_ws = (
                fused_conv.pack_fc_rows_positionwise(
                    (hidden[0].weights_pm1 > 0).astype(np.uint8),
                    n_pos, c_f,
                ),
            ) + layer_ws[1:]
        side, cw0 = image_side, conv_metas[0].cw_in

        def _pack_conv(x01):
            img = jnp.asarray(x01).reshape(-1, side, side)
            words = binarize.pack_bits(enc.encode_bits(img))
            return words.reshape(words.shape[0], side * side * cw0)

        pack = _pack_conv
    elif hidden:
        pack = binarize.pack_pm1
    else:
        from repro.core.cam import query_with_bias

        pack = functools.partial(query_with_bias, bias_cells=head.bias_cells)
    pack_fn = jax.jit(_named(pack, "picbnn_pack"))

    phys = None
    if noise is not None:
        phys = SearchPhysics.for_head(head, noise, params)

    # donation-friendly programs: the packed input is the only per-call
    # buffer worth donating (weights live in the closures)
    donate_kw = {"donate_argnums": (0,)} if donate else {}

    if conv_layers:
        bias_words = (fused_conv.bias_drive_words(head.bias_cells)
                      if head_direct else None)

        def _front(x_packed):
            # [B, S*S*Cw0] -> conv stack -> flattened packed FC query
            x4 = x_packed.reshape(-1, image_side, image_side, cw0)
            return fused_conv.conv_stage_packed(
                x4, conv_ws, conv_cs, conv_metas, bias_words
            )
    else:
        def _front(x_packed):
            return x_packed

    def _hd_xla(x_packed):
        return _head_hd_xla(
            _front(x_packed), layer_ws, layer_cs, layer_n_bits, head_rows,
            head.bias_cells,
        )

    # the two kernel-eligible vote producers (single [B, C] result block)
    if impl == "pallas" and conv_layers:
        def _kernel_votes(x_packed, thr_samples=None):
            return fused_conv.fused_conv_votes(
                x_packed.reshape(-1, image_side, image_side, cw0),
                conv_ws, conv_cs, conv_metas,
                layer_ws, layer_cs, layer_n_bits, head_rows, thresholds,
                bias_cells=head.bias_cells, bq=bq,
                interpret=interpret, head_direct=head_direct,
                thr_samples=thr_samples,
            )
    elif impl == "pallas":
        def _kernel_votes(x_packed, thr_samples=None):
            return fused_mlp.fused_mlp_votes(
                x_packed, layer_ws, layer_cs, layer_n_bits,
                head_rows, thresholds,
                bias_cells=head.bias_cells, bq=bq,
                interpret=interpret, thr_samples=thr_samples,
            )
    else:
        _kernel_votes = None

    def _votes_off(x_packed):
        if _kernel_votes is not None:
            return _kernel_votes(x_packed)
        hd = _hd_xla(x_packed)
        return (hd[:, :, None] <= thresholds[None, None, :]).astype(
            jnp.int32
        ).sum(-1)

    def _votes_batch(x_packed, key):
        # one batch-shaped draw: sampled [P, B, C] thresholds against the
        # single HD computation
        if _kernel_votes is not None:
            t = phys.sample(
                key, batch_shape=(x_packed.shape[0],), n_rows=n_classes
            )  # [P, B, C]
            return _kernel_votes(x_packed, thr_samples=t)
        hd = _hd_xla(x_packed).astype(jnp.float32)  # [B, C]
        t = phys.sample(key, batch_shape=(hd.shape[0],), n_rows=n_classes)
        return (hd[None] <= t).astype(jnp.int32).sum(0)

    # per-request draw: batch_shape=() per row — each row's realization
    # depends only on (x_i, keys_i), never on batch composition or bucket
    # padding (the serve determinism contract)
    def _votes_one(hd_i, k):
        t = phys.sample(k, (), n_classes)  # [P, C]
        return (hd_i[None] <= t).astype(jnp.int32).sum(0)

    def make_program(spec: InferenceSpec) -> Callable:
        """Build the fused program for one spec (jitted; signature per
        the spec's noise axis — see CompiledPipeline.program)."""
        mc = spec.mc_samples

        if spec.cumulative:
            if spec.noise == "off":
                def fn(x_packed):
                    # the exact staircase: per-pass match indicators of
                    # the deterministic compare, cumsum'd over passes
                    hd = _hd_xla(x_packed)
                    per = (hd[None, :, :] <= thresholds[:, None, None])
                    return jnp.cumsum(per.astype(jnp.int32), axis=0)
            else:  # "batch"
                def fn(x_packed, key):
                    hd = _hd_xla(x_packed).astype(jnp.float32)
                    t = phys.sample(key, (hd.shape[0],), n_classes)
                    return jnp.cumsum((hd[None] <= t).astype(jnp.int32),
                                      axis=0)
        elif spec.noise == "off":
            fn = _votes_off
        elif spec.noise == "batch":
            if mc is None:
                fn = _votes_batch
            else:
                def fn(x_packed, key):
                    hd = _hd_xla(x_packed).astype(jnp.float32)  # ONCE

                    def one(k):
                        t = phys.sample(k, (hd.shape[0],), n_classes)
                        return (hd[None] <= t).astype(jnp.int32).sum(0)

                    out = jax.vmap(one)(jax.random.split(key, mc))
                    return out.sum(0) if spec.reduction == "sum" else out
        else:  # "per_request"
            if mc is None:
                def fn(x_packed, keys):
                    hd = _hd_xla(x_packed).astype(jnp.float32)  # [B, C]
                    return jax.vmap(_votes_one)(hd, keys)
            elif spec.reduction == "sum":
                def fn(x_packed, keys):
                    hd = _hd_xla(x_packed).astype(jnp.float32)

                    def per_req(hd_i, k):
                        return jax.vmap(lambda ks: _votes_one(hd_i, ks))(
                            jax.random.split(k, mc)
                        ).sum(0)  # [C] — reduction fused into the program

                    return jax.vmap(per_req)(hd, keys)  # [B, C]
            else:
                def fn(x_packed, keys):
                    hd = _hd_xla(x_packed).astype(jnp.float32)  # ONCE

                    def per_req(hd_i, k):
                        return jax.vmap(lambda ks: _votes_one(hd_i, ks))(
                            jax.random.split(k, mc)
                        )  # [S, C]

                    return jnp.moveaxis(
                        jax.vmap(per_req)(hd, keys), 1, 0
                    )  # [S, B, C] (votes_mc layout)

        if spec.reduction == "argmax":
            base = fn  # single-realization vote producer, [B, C]
            if spec.noise == "off":
                def fn(x_packed):
                    return jnp.argmax(base(x_packed), axis=-1)
            else:
                def fn(x_packed, rng):
                    return jnp.argmax(base(x_packed, rng), axis=-1)

        return jax.jit(_named(fn, spec.program_name), **donate_kw)

    if conv_layers:
        n_in = int(image_side) ** 2  # raw [0,1] pixels in, encode inside
    elif hidden:
        n_in = int(hidden[0].n_in)
    else:
        n_in = int(out_layer.n_in)
    return CompiledPipeline(
        head=head,
        n_in=n_in,
        n_classes=n_classes,
        impl=impl,
        min_bucket=min_bucket,
        head_only=not hidden,
        physics=phys,
        _program_factory=make_program,
        _pack_fn=pack_fn,
        max_bucket=max_bucket,
    )
