"""Deployment artifact: the persistable bundle behind one compiled BNN.

The paper's deployment story is a single artifact — a folded, bit-packed
BNN written once into the CAM, then queried under one search contract
(Algorithm 1 with knob-configured noise).  :class:`Deployment` is that
artifact for this repo: folded layers + binary input encoding +
`EnsembleConfig` + `NoiseModel`/`AnalogParams` + compile options, with

  * one constructor for MLP and CNN deployments alike
    (:func:`deploy` — takes folded layers, or trained params + config
    and folds them);
  * lazy compilation: `.pipeline()` builds the fused
    `pipeline.CompiledPipeline` on first use, which itself compiles one
    program per `repro.spec.InferenceSpec` on demand;
  * persistence through the existing `checkpoint/ckpt.py` machinery:
    `save(dir)` writes `deployment.json` (the declarative config +
    layer topology) plus an atomic checkpoint step of BIT-PACKED
    weights; `Deployment.load(dir)` reconstructs a deployment whose
    `run(x, spec)` is bit-identical to the original
    (tests/test_deploy.py proves this on all three bank configurations
    and the CNN configs, noiseless and per-request silicon).

Serving integration: `serve.picbnn.PicBnnServer.register` accepts a
live `Deployment` or a saved deployment directory, so servers register
models straight from disk.

On-disk layout::

    <dir>/deployment.json       declarative config (schema
                                picbnn-deployment/v1): layer topology
                                with each conv layer's per-channel
                                integers (pool signs, magnitudes, RSign
                                alpha, RPReLU gamma/q/zeta),
                                ensemble/noise/encoding/compile options
    <dir>/step_00000000/        ckpt.save output — manifest.json + one
                                .npy per leaf: packed uint32 weight
                                words + int32 C_j constants per layer
                                (and its downsample conv's, `down_w`,
                                `down_c`, for a residual layer)

The weight files hold `pack_bits`-packed rows (32 weights per uint32
word, little-endian) — 32x smaller than the ±1 int8 form and exactly
what the CAM write would consume.  Unpacking on load is bit-exact by
construction (weights are ±1, so `w > 0` is invertible).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional, Sequence, Union

import jax
import numpy as np

from repro import pipeline as _pipeline
from repro.checkpoint import ckpt
from repro.core import binarize, bnn, convnet
from repro.core.binarize import InputEncoding
from repro.core.bnn import FoldedLayer, MLPConfig
from repro.core.convnet import CNNConfig, FoldedConvLayer
from repro.core.device_model import AnalogParams, NoiseModel
from repro.core.ensemble import EnsembleConfig
from repro.spec import InferenceSpec

SCHEMA = "picbnn-deployment/v1"

#: compile_pipeline options a Deployment may carry (everything except
#: the model/physics inputs, which are first-class Deployment fields)
COMPILE_OPTIONS = ("bq", "min_bucket", "max_bucket", "donate")


def _np_unpack_bits(words: np.ndarray, n_bits: int) -> np.ndarray:
    """NumPy twin of binarize.unpack_bits (little-endian uint32 words)."""
    shifts = np.arange(32, dtype=np.uint32)
    bits = (words[..., None] >> shifts) & np.uint32(1)
    return bits.reshape(*words.shape[:-1], -1)[..., :n_bits].astype(np.uint8)


def _pack_rows(weights_pm1: np.ndarray) -> np.ndarray:
    """±1 weight rows (any trailing shape) -> packed uint32 words."""
    rows = np.asarray(weights_pm1).reshape(weights_pm1.shape[0], -1)
    return binarize.np_pack_bits((rows > 0).astype(np.uint8))


def _unpack_rows(words: np.ndarray, shape: Sequence[int]) -> np.ndarray:
    """Inverse of `_pack_rows`: packed words -> ±1 int8 of `shape`."""
    shape = tuple(int(s) for s in shape)
    n_bits = int(np.prod(shape[1:]))
    bits = _np_unpack_bits(np.asarray(words), n_bits)
    return (bits.astype(np.int8) * 2 - 1).reshape(shape)


def _ints(a) -> Optional[list]:
    """A per-channel integer array as JSON (None stays None)."""
    return None if a is None else [int(v) for v in a]


def _array(v, dtype) -> Optional[np.ndarray]:
    """Inverse of `_ints`."""
    return None if v is None else np.asarray(v, dtype)


@dataclasses.dataclass
class Deployment:
    """A persistable deployed BNN: model + physics + compile config.

    Construct with :func:`deploy` (or :meth:`load`); treat as immutable.
    `pipeline()` compiles lazily and caches; `run()` / `warmup()`
    delegate to it, so a Deployment is used exactly like a
    `CompiledPipeline` — plus `save()`.
    """

    folded: tuple  # FoldedConvLayer prefix + FoldedLayer tail
    ens_cfg: EnsembleConfig
    noise: Optional[NoiseModel] = None
    params: Optional[AnalogParams] = None
    image_side: Optional[int] = None
    image_encoding: Optional[InputEncoding] = None
    image_channels: Optional[int] = None  # conv: pixel channels (HWC)
    compile_options: dict = dataclasses.field(default_factory=dict)
    _pipe: Optional[_pipeline.CompiledPipeline] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self):
        unknown = set(self.compile_options) - set(COMPILE_OPTIONS)
        if unknown:
            raise ValueError(
                f"unknown compile options {sorted(unknown)}; "
                f"known: {COMPILE_OPTIONS}"
            )

    # ------------------------------------------------------------------
    # model topology
    # ------------------------------------------------------------------
    @property
    def conv_layers(self) -> tuple:
        """The FoldedConvLayer prefix (empty for MLP deployments)."""
        return tuple(l for l in self.folded
                     if isinstance(l, FoldedConvLayer))

    @property
    def layer_sizes(self) -> Optional[tuple[int, ...]]:
        """(n_in, ..., n_classes) for pure-MLP deployments, else None.

        Serving uses this to derive the Table-II silicon-equivalent
        throughput without the caller restating the topology.
        """
        if self.conv_layers:
            return None
        fc = [l for l in self.folded]
        return (int(fc[0].n_in),) + tuple(int(l.n_out) for l in fc)

    # ------------------------------------------------------------------
    # lazy compilation + execution
    # ------------------------------------------------------------------
    def pipeline(self) -> _pipeline.CompiledPipeline:
        """The compiled pipeline (built on first call, then cached).

        Program compilation is itself lazy per `InferenceSpec` — a
        deployment only pays XLA compile time for the specs it actually
        runs (or warms).
        """
        if self._pipe is None:
            kw = dict(self.compile_options)
            if self.image_side is not None:
                kw["image_side"] = self.image_side
                kw["image_encoding"] = self.image_encoding
                kw["image_channels"] = self.image_channels
            self._pipe = _pipeline.compile_pipeline(
                list(self.folded), self.ens_cfg,
                noise=self.noise, params=self.params, **kw
            )
        return self._pipe

    def run(self, x: jax.Array, spec: InferenceSpec, *,
            key: Optional[jax.Array] = None,
            keys: Optional[jax.Array] = None) -> jax.Array:
        """`CompiledPipeline.run` on the (lazily compiled) pipeline."""
        return self.pipeline().run(x, spec, key=key, keys=keys)

    def warmup(self, max_batch: int, **kw):
        """`CompiledPipeline.warmup` on the (lazily compiled) pipeline."""
        return self.pipeline().warmup(max_batch, **kw)

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------
    def save(self, root: Union[str, Path]) -> Path:
        """Persist to `root/`: packed-weight checkpoint + manifest.

        The weight arrays go through `checkpoint.ckpt.save` (atomic
        tmp-dir + rename), then `deployment.json` is written — its
        presence marks a complete artifact.  Returns `root` as a Path.
        """
        root = Path(root)
        tree = {"layers": []}
        layers_meta = []
        for layer in self.folded:
            leaves = {"w": _pack_rows(layer.weights_pm1),
                      "c": np.asarray(layer.c, np.int32)}
            if isinstance(layer, FoldedConvLayer):
                d = layer.down
                layers_meta.append({
                    "kind": "conv",
                    "shape": list(layer.weights_pm1.shape),
                    "stride": int(layer.stride),
                    "padding": layer.padding,
                    "pool": int(layer.pool),
                    "pool_sign": _ints(layer.pool_sign),
                    "residual": _ints(layer.residual),
                    "shortcut": layer.shortcut,
                    "down": None if d is None else {
                        "shape": list(d.weights_pm1.shape),
                        "residual": _ints(d.residual)},
                    "alpha": _ints(layer.alpha),
                    "rprelu": None if layer.rprelu is None else {
                        k: _ints(getattr(layer.rprelu, k))
                        for k in ("gamma", "q", "zeta")},
                })
                if d is not None:
                    leaves.update(down_w=_pack_rows(d.weights_pm1),
                                  down_c=np.asarray(d.c, np.int32))
            else:
                layers_meta.append({
                    "kind": "fc",
                    "shape": list(layer.weights_pm1.shape),
                })
            tree["layers"].append(leaves)
        ckpt.save(root, step=0, tree=tree)
        manifest = {
            "schema": SCHEMA,
            "layers": layers_meta,
            "ens_cfg": {
                "thresholds": [int(t) for t in self.ens_cfg.thresholds],
                "bias_cells": int(self.ens_cfg.bias_cells),
                "mode": self.ens_cfg.mode,
                "calibrated": bool(self.ens_cfg.calibrated),
                # the pipeline itself ignores ens_cfg.noise (physics come
                # from Deployment.noise), but load(save(d)).ens_cfg must
                # equal d.ens_cfg — faithful round trip, field by field
                "noise": dataclasses.asdict(self.ens_cfg.noise),
            },
            "noise": (None if self.noise is None
                      else dataclasses.asdict(self.noise)),
            "analog_params": (None if self.params is None
                              else dataclasses.asdict(self.params)),
            "image_side": self.image_side,
            "image_channels": self.image_channels,
            "image_encoding": (None if self.image_encoding is None else {
                "kind": self.image_encoding.kind,
                "width": int(self.image_encoding.width),
            }),
            "compile_options": self.compile_options,
        }
        (root / "deployment.json").write_text(json.dumps(manifest, indent=1))
        return root

    @classmethod
    def load(cls, root: Union[str, Path]) -> "Deployment":
        """Reconstruct a Deployment saved by :meth:`save`.

        Bit-exactness contract: `load(d.save(p)).run(x, spec)` equals
        `d.run(x, spec)` bit-for-bit for every spec (the weights are ±1,
        so packing is invertible; every config field round-trips through
        JSON exactly).
        """
        root = Path(root)
        mf_path = root / "deployment.json"
        if not mf_path.exists():
            raise FileNotFoundError(
                f"{root} is not a deployment directory (no deployment.json)"
            )
        mf = json.loads(mf_path.read_text())
        if mf.get("schema") != SCHEMA:
            raise ValueError(
                f"unsupported deployment schema {mf.get('schema')!r} "
                f"(expected {SCHEMA})"
            )

        def leaves(shape, prefix=""):
            n_rows, n_bits = int(shape[0]), int(np.prod(shape[1:]))
            return {
                prefix + "w": jax.ShapeDtypeStruct(
                    (n_rows, binarize.packed_width(n_bits)), np.uint32),
                prefix + "c": jax.ShapeDtypeStruct((n_rows,), np.int32),
            }

        template = {"layers": []}
        for lm in mf["layers"]:
            entry = leaves(lm["shape"])
            if lm.get("down"):
                entry.update(leaves(lm["down"]["shape"], "down_"))
            template["layers"].append(entry)
        tree, _step = ckpt.restore(root, None, template)
        folded = []
        for lm, leaf in zip(mf["layers"], tree["layers"]):
            w = _unpack_rows(np.asarray(leaf["w"]), lm["shape"])
            c = np.asarray(leaf["c"], np.int64)
            if lm["kind"] == "conv":
                dm, rp = lm.get("down"), lm.get("rprelu")
                folded.append(FoldedConvLayer(
                    weights_pm1=w, c=c, stride=int(lm["stride"]),
                    padding=lm.get("padding", "valid"),
                    pool=int(lm.get("pool", 1)),
                    pool_sign=_array(lm.get("pool_sign"), np.int8),
                    residual=_array(lm.get("residual"), np.int64),
                    shortcut=lm.get("shortcut", "none"),
                    down=None if dm is None else FoldedConvLayer(
                        weights_pm1=_unpack_rows(
                            np.asarray(leaf["down_w"]), dm["shape"]),
                        c=np.asarray(leaf["down_c"], np.int64),
                        residual=_array(dm["residual"], np.int64)),
                    alpha=_array(lm.get("alpha"), np.int64),
                    rprelu=None if rp is None else convnet.RPReLU(
                        **{k: _array(v, np.int64) for k, v in rp.items()}),
                ))
            else:
                folded.append(FoldedLayer(weights_pm1=w, c=c))
        ecd = mf["ens_cfg"]
        enc = mf["image_encoding"]
        return cls(
            folded=tuple(folded),
            ens_cfg=EnsembleConfig(
                thresholds=tuple(ecd["thresholds"]),
                bias_cells=ecd["bias_cells"],
                mode=ecd["mode"],
                calibrated=ecd["calibrated"],
                noise=NoiseModel(**ecd["noise"]),
            ),
            noise=(None if mf["noise"] is None
                   else NoiseModel(**mf["noise"])),
            params=(None if mf["analog_params"] is None
                    else AnalogParams(**mf["analog_params"])),
            image_side=mf["image_side"],
            image_channels=mf.get("image_channels"),
            image_encoding=(None if enc is None
                            else InputEncoding(enc["kind"], enc["width"])),
            compile_options=dict(mf["compile_options"]),
        )


def is_deployment_dir(path: Union[str, Path]) -> bool:
    """True when `path` holds a saved Deployment (has deployment.json)."""
    return (Path(path) / "deployment.json").exists()


def deploy(
    model,
    *,
    config: Union[MLPConfig, CNNConfig, None] = None,
    ens_cfg: Optional[EnsembleConfig] = None,
    noise: Optional[NoiseModel] = None,
    params: Optional[AnalogParams] = None,
    image_side: Optional[int] = None,
    image_encoding: Optional[InputEncoding] = None,
    image_channels: Optional[int] = None,
    **compile_options,
) -> Deployment:
    """Build a `Deployment` from a model — MLP and CNN configs alike.

    model : either already-folded layers (`bnn.fold` / `convnet.fold_cnn`
        / `convnet.random_folded_cnn` output: an optional
        `FoldedConvLayer` prefix + `FoldedLayer` tail), or a TRAINED
        params dict — then `config` is required and the fold runs here
        (`bnn.fold` for `MLPConfig`, `convnet.fold_cnn` for `CNNConfig`).
    config : optional `MLPConfig` | `CNNConfig`; supplies the defaults a
        hand-rolled call would restate — `bias_cells` for the ensemble
        config, and (CNN) the image side, channels and binary input
        encoding.
    ens_cfg / noise / params / image_side / image_encoding /
    image_channels : as `pipeline.compile_pipeline`; explicit arguments
        win over config-derived defaults.
    compile_options : forwarded to `compile_pipeline` at (lazy) compile
        time — one of `deploy.COMPILE_OPTIONS` (bq, min_bucket,
        max_bucket, donate).  Whether the Pallas kernel or the XLA twin
        produces the votes is derived from the backend and the graph
        (see `repro.pipeline`), not an option.

    >>> d = deploy(bnn.fold(params, cfg), config=cfg, noise=SILICON)
    >>> d.run(x, InferenceSpec(noise="per_request"), keys=keys)
    >>> d.save("ckpts/mnist")       # serve later:
    >>> server.register("mnist", "ckpts/mnist")
    """
    if isinstance(model, dict):
        if isinstance(config, CNNConfig):
            folded = convnet.fold_cnn(model, config)
        elif isinstance(config, MLPConfig):
            folded = bnn.fold(model, config)
        else:
            raise ValueError(
                "deploy(params_dict) needs config=MLPConfig|CNNConfig "
                "to fold the trained parameters"
            )
    else:
        folded = list(model)
    if isinstance(config, CNNConfig):
        image_side = config.side if image_side is None else image_side
        image_encoding = (config.encoding if image_encoding is None
                          else image_encoding)
        image_channels = (config.channels if image_channels is None
                          else image_channels)
    if ens_cfg is None:
        bias = getattr(config, "bias_cells", None)
        ens_cfg = (EnsembleConfig(bias_cells=bias) if bias is not None
                   else EnsembleConfig())
    return Deployment(
        folded=tuple(folded),
        ens_cfg=ens_cfg,
        noise=noise,
        params=params,
        image_side=image_side,
        image_encoding=image_encoding,
        image_channels=image_channels,
        compile_options=compile_options,
    )
