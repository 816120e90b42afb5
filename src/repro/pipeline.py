"""End-to-end deployed-BNN inference pipeline (packed domain, fused).

`compile_pipeline(folded, ens_cfg)` turns a folded binary MLP (list of
`bnn.FoldedLayer`) plus an Algorithm-1 ensemble config into a jitted
batch classifier driven by a declarative request spec
(`repro.spec.InferenceSpec`):

    pipe = compile_pipeline(folded, EnsembleConfig())
    votes = pipe.run(x_pm1, InferenceSpec())              # [B, C] int32
    pred  = pipe.run(x_pm1, InferenceSpec(reduction="argmax"))  # [B]

`run(x, spec, key=..., keys=...)` is the one way in (`run_packed` for
an input already in its program's form): it compiles and caches exactly
one fused program per distinct spec, and holds the batch bucketing,
pad/trim, and PRNG-key shape logic for every spec.

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
staircase, valid on ANY pipeline.  With `noise=NOISELESS` every noisy
spec is bit-identical to the noiseless oracle (tested).

Two producers of the Hamming distances and votes, and one rule between
them: the Pallas kernel (`kernels/fused_mlp.py`: one launch per batch
block, hidden activations resident in VMEM) produces the votes of the
noise-off and batch-noise single-realization specs of a graph with no
conv layers when `jax.default_backend() == "tpu"`.  Every other case
runs the XLA twin: the same packed-domain math as one jitted program
(`_head_hd_xla`; `fused_conv.net_hd` for conv graphs), whose noisy path
broadcasts the sampled [P, B, C] thresholds against the one HD
computation.  Monte-Carlo, cumulative and per-request outputs do not fit
the kernel's single [B, C] result block.  The kernel is fed a
precomputed [P, B, C] threshold-sample operand, so randomness never
enters it, and the twins are bit-exact equal: the choice is scheduling,
not semantics.  The pipeline never runs the kernel off the TPU; its CPU
coverage is direct (tests/test_fused_mlp.py, interpret mode).

Convolutional graphs: `folded` may start with a prefix of
`convnet.FoldedConvLayer` (a deployed end-to-end-binary CNN, e.g.
`convnet.fold_cnn` output; plain layers, or residual layers that carry
an int32 stream with identity and downsample shortcuts, as Bi-Real
Net's binary ResNet).  The pipeline then takes RAW [0,1] pixels
[B, side*side*channels] (HWC), and each vote program takes them as they
are staged: it runs the binary input layer (`image_encoding`,
thermometer by default) to ±1 int8 maps itself, then the whole net,
conv stack, FC layers and head distances, as ±1 int8 products on the
MXU (`kernels/fused_conv.py`) on every backend, so every spec works
identically for conv and MLP deployments.  Its weights are arguments of
the programs, not jit constants.  Bit-exactness bar: the unpacked
oracle `kernels.ref.conv_votes_ref` (tests/test_conv.py).

Batch-size bucketing: inputs are zero-padded up to the next bucket
(powers of two, floor `min_bucket`) so a serving loop with ragged batch
sizes compiles O(log B) program variants instead of one per size.

Persistable deployments (`repro.deploy.Deployment`) bundle the folded
layers + encoding + configs this function takes, and rebuild the same
pipeline from disk — see deploy.py.

Where the input is packed: a host array given to an MLP with hidden
layers is packed on the host (`binarize.pack_pm1_host`, bit-equal to
the device pack), so only its uint32 words cross to the device, 1/32 of
the float32 bytes.  An MLP's `jax.Array` (already on the device, as the
server stages its batches) and a head-only pipeline's input are staged
as they are and packed by the `picbnn_pack` program on the device.  A
conv pipeline has no packed form: its float32 pixels are staged and the
vote program encodes them.

Observability (`repro.obs`): each `run` is a `picbnn.run` profiler span
with the pipeline's call number (`call=`).  For a host MLP input it
encloses `picbnn.host_pack` (counted in `pack.host_*`), `picbnn.stage`
(host->device copy of the words, counted in `stage.*`), `picbnn.pad`
(padded batches only) and `picbnn.vote`; for a conv pipeline
`picbnn.stage` (host arrays only), `picbnn.pad` and `picbnn.vote`; for
the other inputs `picbnn.stage` (host arrays only), `picbnn.pack`,
`picbnn.pad` and `picbnn.vote`.  `picbnn.pack` and `picbnn.vote` are
each the dispatch of one program; each `picbnn.pack` adds one to the
counter `pack.device_calls`.  Each vote dispatch adds the weight bytes
its program reads from HBM, as the program lays them out (once per
call), to the counter `kernel.weight_bytes`, the rows it answers to
`kernel.rows`, the residual stream its program writes over the
bucket's rows, as elements at 4 B, the width its values are proven
against, to `kernel.stream_bytes` (0 without residual layers,
`fused_conv.stream_bytes_per_row`), and the bytes the program stores
for that stream over the bucket's rows, at each layer's own width
(int16 or int32, `ConvMeta.stream_dtype`), to
`kernel.stream_stored_bytes` (0 without residual layers,
`fused_conv.stream_stored_bytes_per_row`).  The programs carry fixed
names:
`picbnn_pack`, and one `InferenceSpec.program_name` per spec
(`picbnn_votes_off`, ...), so a device trace names them `jit_picbnn_*`.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
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
from repro.spec import InferenceSpec


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
    def named(*args, **kw):
        return fn(*args, **kw)

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


@dataclasses.dataclass
class CompiledPipeline:
    """A jitted end-to-end batch classifier for one deployed BNN.

    The execution surface is `run(x, spec)` / `run_packed(x_packed,
    spec)`: one fused XLA program is compiled and cached per distinct
    `InferenceSpec` (`program(spec)` is the cache), and all bucketing /
    padding / result trimming / PRNG-key validation lives in `run_packed`
    — once, for every spec.
    """

    head: CAMEnsembleHead
    n_in: int
    n_classes: int
    min_bucket: int
    head_only: bool  # no hidden layers: input feeds the CAM head directly
    physics: Optional[SearchPhysics]  # None <=> compiled without noise=
    _program_factory: Callable  # InferenceSpec -> jitted program
    # the jitted `picbnn_pack` program, ±1 [B, n_in] -> packed words;
    # None for conv graphs, whose vote program takes the pixels
    _pack_fn: Optional[Callable]
    # the same pack for host arrays, on the host (MLPs with hidden layers:
    # `binarize.pack_pm1_host`); None packs every input on the device
    _host_pack: Optional[Callable] = None
    max_bucket: Optional[int] = None  # serving cap on the bucket grid
    # weight operands every program takes as `ops=` (conv graphs; MLP
    # programs hold theirs as constants), and the bytes a call reads
    weight_operands: tuple = ()
    weight_bytes: int = 0
    # residual stream a program writes per (bucket) row: its elements at
    # 4 B, and the bytes it stores at each layer's own width
    stream_bytes_per_row: int = 0
    stream_stored_bytes_per_row: int = 0
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
        "per_request", each with the weight operands as `ops=` —
        `run_packed` dispatches accordingly.  Callers
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
               pixels [B, side*side*channels] (HWC) for conv pipelines
               (staged as float32; the vote program runs the binary
               input encoding).  A host array given to an MLP with
               hidden layers is packed on the host and its uint32 words
               are staged; an MLP's `jax.Array`, and every input of a
               head-only pipeline, is packed on the device.
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
        """`run` for an input batch already in its program's form.

        MLP and head-only pipelines: the packed words [B, Kw0] that
        `_pack_input` emits.  Conv pipelines: the raw [0,1] pixels
        [B, side*side*channels] float32 (HWC), which the vote program
        encodes itself — a conv pipeline has no packed form.
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
        ops = self.weight_operands
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
                out = prog(x_packed, keys, ops=ops)
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
                out = prog(x_packed, key, ops=ops)
        else:
            if key is not None or keys is not None:
                raise ValueError(
                    f'{spec.describe()} is deterministic (noise="off"): '
                    "it accepts neither key= nor keys="
                )
            with obs.span("picbnn.vote", call=call):
                out = prog(x_packed, ops=ops)
        obs.count("kernel.weight_bytes", self.weight_bytes)
        obs.count("kernel.rows", b)
        obs.count("kernel.stream_bytes",
                  self.stream_bytes_per_row * x_packed.shape[0])
        obs.count("kernel.stream_stored_bytes",
                  self.stream_stored_bytes_per_row * x_packed.shape[0])
        return self._trim(out, b, spec.batch_axis)

    # ------------------------------------------------------------------
    # shared glue (bucketing / packing / trimming / key shapes)
    # ------------------------------------------------------------------
    def _pack_input(self, x_pm1: jax.Array, call: int) -> jax.Array:
        if self._host_pack is not None and not isinstance(x_pm1, jax.Array):
            # a host ±1 batch keeps one bit of each value: pack it here
            # and stage 1/32 of the float32 bytes
            rows = len(x_pm1)
            t0 = time.perf_counter_ns()
            with obs.span("picbnn.host_pack", call=call, rows=rows):
                words = self._host_pack(x_pm1)
            obs.count("pack.host_ns", time.perf_counter_ns() - t0)
            obs.count("pack.host_calls")
            obs.count("pack.host_rows", rows)
            return obs.stage(words, call=call)
        x = obs.stage(x_pm1, call=call)
        if self._pack_fn is None:  # conv: the vote program encodes pixels
            return x
        # one jitted dispatch: the eager op-by-op pack costs ~5x the whole
        # fused vote program in host dispatch overhead (serving hot path)
        obs.count("pack.device_calls")
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
               mc_samples: Optional[int] = None, device=None
               ) -> dict[tuple[InferenceSpec, int], float]:
        """Precompile every (spec, bucket) program a serving loop needs.

        Runs one dummy batch per (spec, bucket) pair and blocks until
        ready, so first-request compile latency never shows up in served
        percentiles.

        specs   : the request specs to warm; default
            `default_warmup_specs(mc_samples)`.  A serving loop passes
            exactly its dispatch spec(s) — startup time is
            specs x buckets x devices XLA compiles.
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


def compile_pipeline(
    folded: Sequence,
    ens_cfg: EnsembleConfig | None = None,
    *,
    bq: int | None = None,
    min_bucket: int = 64,
    max_bucket: int | None = None,
    noise: NoiseModel | None = None,
    params=None,
    donate: bool = False,
    image_side: int | None = None,
    image_encoding: binarize.InputEncoding | None = None,
    image_channels: int | None = None,
) -> CompiledPipeline:
    """Compile a folded BNN + ensemble head into a fused batch classifier.

    folded  : `bnn.fold` output — hidden layers + the output layer (last).
              May start with a prefix of `convnet.FoldedConvLayer`
              (`convnet.fold_cnn` output): the pipeline then runs the
              end-to-end-binary CNN workload and its input domain becomes
              RAW [0,1] pixels [B, image_side**2 * image_channels] (the
              binary input encoding runs inside the vote program).
    ens_cfg : Algorithm-1 config (thresholds / bias cells); default paper's.
    bq      : Pallas batch rows per kernel block; default 128, one vreg
              of lanes (the batch is the kernels' lane axis — DESIGN.md
              §4 and §10 derive the VMEM budgets).  Compiled blocks need
              a multiple of 128.  Read only where the kernel runs (see
              the module doc for the one rule that decides).
    noise   : optional NoiseModel — enables the silicon-mode specs
              (noise="batch"/"per_request", Monte-Carlo, noisy
              cumulative) by building a SearchPhysics bundle from the
              head's threshold schedule; `params` optionally overrides
              the AnalogParams.  noise=None keeps the pipeline
              noiseless-only (no knob-schedule work at compile time).
    max_bucket : optional cap on the batch-bucket grid (see next_bucket);
              serving loops set it to their max batch so warmup() closes
              the compiled-variant set.
    donate  : donate the program's input buffer to the compiled programs
              (donate_argnums) — the packing step or the staging copy
              produces a fresh buffer per call, so a serving loop can
              hand it to the program and save an allocation on TPU/GPU.
              No effect on results; backends that can't reuse the
              buffer (CPU) just ignore the donation.  Off by default
              because `run_packed` is public API and donation
              invalidates the caller's array (as it does a `jax.Array`
              given to a conv pipeline's `run`, which is not copied).
    image_side : REQUIRED for conv graphs — square input image side
              (`n_in = image_side**2 * image_channels` raw pixels).
              Rejected for pure MLP graphs.
    image_encoding : the binary input layer for conv graphs
              (`binarize.InputEncoding`); its width times
              `image_channels` must equal the first conv layer's c_in.
              Default: thermometer of c_in / image_channels.
    image_channels : input channels per pixel of a conv graph (HWC
              rows; default 1).  Rejected for pure MLP graphs.

    The returned pipeline compiles lazily: `run(x, spec)` builds one
    fused program per distinct `InferenceSpec` on first use (warmup()
    precompiles a chosen set).  `repro.deploy.deploy(...)` wraps this
    call in a persistable `Deployment` artifact.
    """
    ens_cfg = ens_cfg or EnsembleConfig()
    if len(folded) < 1:
        raise ValueError("need at least the output layer")

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
                            or image_encoding is not None
                            or image_channels is not None):
        raise ValueError(
            "image_side/image_encoding/image_channels are conv-only options")
    channels = 1 if image_channels is None else int(image_channels)

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

    host_pack = None
    operands: tuple = ()
    stream_bytes = stream_stored_bytes = 0
    weight_bytes = sum(int(a.size) * a.dtype.itemsize
                       for a in (*layer_ws, *layer_cs, head_rows))
    if conv_layers:
        c0 = conv_layers[0].c_in
        enc = image_encoding or binarize.InputEncoding(
            "thermometer", c0 // channels
        )
        if enc.width * channels != c0:
            raise ValueError(
                f"encoding width {enc.width} x {channels} channel(s) != "
                f"first conv c_in {c0}"
            )
        side = int(image_side)
        conv_metas = fused_conv.conv_metas_for(conv_layers, side)
        mf = conv_metas[-1]
        n_feat = mf.out_side * mf.out_side * mf.c_out
        first_fc = hidden[0] if hidden else out_layer
        if int(first_fc.n_in) != n_feat:
            raise ValueError(
                f"first FC layer n_in {first_fc.n_in} != flattened conv "
                f"features {mf.out_side}^2*{mf.c_out}"
            )
        operands = (
            tuple(fused_conv.conv_operands(l) for l in conv_layers),
            tuple(fused_conv.fc_operands(l) for l in hidden),
            fused_conv.head_operands(
                head_rows, int(hidden[-1].n_out) if hidden else n_feat,
                head.bias_cells),
        )
        weight_bytes = fused_conv.weight_bytes(operands)
        stream_bytes = fused_conv.stream_bytes_per_row(conv_metas)
        stream_stored_bytes = fused_conv.stream_stored_bytes_per_row(
            conv_metas)
        pack = None  # the vote program encodes the staged pixels
    elif hidden:
        pack, host_pack = binarize.pack_pm1, binarize.pack_pm1_host
    else:
        from repro.core.cam import query_with_bias

        pack = functools.partial(query_with_bias, bias_cells=head.bias_cells)
    pack_fn = None if pack is None else jax.jit(_named(pack, "picbnn_pack"))

    phys = None
    if noise is not None:
        phys = SearchPhysics.for_head(head, noise, params)

    # donation-friendly programs: the packed input is the only per-call
    # buffer worth donating (the weights are reused by every call)
    donate_kw = {"donate_argnums": (0,)} if donate else {}

    if conv_layers:
        def _hd_xla(x01, ops):
            return fused_conv.net_hd(x01, ops, conv_metas, side, channels,
                                     enc)
    else:
        def _hd_xla(x_packed, ops):
            return _head_hd_xla(
                x_packed, layer_ws, layer_cs, layer_n_bits, head_rows,
                head.bias_cells,
            )

    # the one kernel-or-twin rule: the Pallas kernel produces the single
    # [B, C] result block of an MLP's noise-off and batch-noise votes on
    # the TPU; every other spec, graph and backend runs the XLA twin
    if jax.default_backend() == "tpu" and not conv_layers:
        def _kernel_votes(x_packed, thr_samples=None):
            return fused_mlp.fused_mlp_votes(
                x_packed, layer_ws, layer_cs, layer_n_bits,
                head_rows, thresholds,
                bias_cells=head.bias_cells, bq=bq,
                thr_samples=thr_samples,
            )
    else:
        _kernel_votes = None

    def _votes_off(x_packed, ops=()):
        if _kernel_votes is not None:
            return _kernel_votes(x_packed)
        hd = _hd_xla(x_packed, ops)
        return (hd[:, :, None] <= thresholds[None, None, :]).astype(
            jnp.int32
        ).sum(-1)

    def _votes_batch(x_packed, key, ops=()):
        # one batch-shaped draw: sampled [P, B, C] thresholds against the
        # single HD computation
        if _kernel_votes is not None:
            t = phys.sample(
                key, batch_shape=(x_packed.shape[0],), n_rows=n_classes
            )  # [P, B, C]
            return _kernel_votes(x_packed, thr_samples=t)
        hd = _hd_xla(x_packed, ops).astype(jnp.float32)  # [B, C]
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
                def fn(x_packed, ops=()):
                    # the exact staircase: per-pass match indicators of
                    # the deterministic compare, cumsum'd over passes
                    hd = _hd_xla(x_packed, ops)
                    per = (hd[None, :, :] <= thresholds[:, None, None])
                    return jnp.cumsum(per.astype(jnp.int32), axis=0)
            else:  # "batch"
                def fn(x_packed, key, ops=()):
                    hd = _hd_xla(x_packed, ops).astype(jnp.float32)
                    t = phys.sample(key, (hd.shape[0],), n_classes)
                    return jnp.cumsum((hd[None] <= t).astype(jnp.int32),
                                      axis=0)
        elif spec.noise == "off":
            fn = _votes_off
        elif spec.noise == "batch":
            if mc is None:
                fn = _votes_batch
            else:
                def fn(x_packed, key, ops=()):
                    hd = _hd_xla(x_packed, ops).astype(jnp.float32)  # ONCE

                    def one(k):
                        t = phys.sample(k, (hd.shape[0],), n_classes)
                        return (hd[None] <= t).astype(jnp.int32).sum(0)

                    out = jax.vmap(one)(jax.random.split(key, mc))
                    return out.sum(0) if spec.reduction == "sum" else out
        else:  # "per_request"
            if mc is None:
                def fn(x_packed, keys, ops=()):
                    hd = _hd_xla(x_packed, ops).astype(jnp.float32)  # [B, C]
                    return jax.vmap(_votes_one)(hd, keys)
            elif spec.reduction == "sum":
                def fn(x_packed, keys, ops=()):
                    hd = _hd_xla(x_packed, ops).astype(jnp.float32)

                    def per_req(hd_i, k):
                        return jax.vmap(lambda ks: _votes_one(hd_i, ks))(
                            jax.random.split(k, mc)
                        ).sum(0)  # [C] — reduction fused into the program

                    return jax.vmap(per_req)(hd, keys)  # [B, C]
            else:
                def fn(x_packed, keys, ops=()):
                    hd = _hd_xla(x_packed, ops).astype(jnp.float32)  # ONCE

                    def per_req(hd_i, k):
                        return jax.vmap(lambda ks: _votes_one(hd_i, ks))(
                            jax.random.split(k, mc)
                        )  # [S, C]

                    return jnp.moveaxis(
                        jax.vmap(per_req)(hd, keys), 1, 0
                    )  # [S, B, C], as the batch-draw MC spec

        if spec.reduction == "argmax":
            base = fn  # single-realization vote producer, [B, C]
            if spec.noise == "off":
                def fn(x_packed, ops=()):
                    return jnp.argmax(base(x_packed, ops), axis=-1)
            else:
                def fn(x_packed, rng, ops=()):
                    return jnp.argmax(base(x_packed, rng, ops), axis=-1)

        return jax.jit(_named(fn, spec.program_name), **donate_kw)

    if conv_layers:
        n_in = side * side * channels  # raw [0,1] pixels in, encode inside
    elif hidden:
        n_in = int(hidden[0].n_in)
    else:
        n_in = int(out_layer.n_in)
    return CompiledPipeline(
        head=head,
        n_in=n_in,
        n_classes=n_classes,
        min_bucket=min_bucket,
        head_only=not hidden,
        physics=phys,
        _program_factory=make_program,
        _pack_fn=pack_fn,
        _host_pack=host_pack,
        max_bucket=max_bucket,
        weight_operands=operands,
        weight_bytes=weight_bytes,
        stream_bytes_per_row=stream_bytes,
        stream_stored_bytes_per_row=stream_stored_bytes,
    )
