"""Smoke run of the PiC-BNN serving path on a TPU.

    python chip_smoke.py                # one chip: kernels, pipelines, server
    python chip_smoke.py --four-chips   # one server over four local chips

Drives the main path through the entry points a user calls —
`deploy_mlp` / `deploy_cnn` -> `Deployment.pipeline()` -> `run(x, spec)`
-> `PicBnnServer` — at the full widths of the repo's six deployments
(`configs/paper_mlp.py`, `configs/paper_cnn.py`; Bi-Real-18 and
ReActNet-A carry the residual stream), with weights made from
`--seed`.  Each phase prints one line and checks its results exactly:

  * the fused Pallas kernel (MLPs) and the int8 MXU program (CNNs)
    against the float32 oracles, evaluated on
    the host CPU backend (`bnn.folded_forward_exact` +
    `ensemble.votes_fused` for the MLPs, `kernels.ref.conv_votes_ref`
    for the CNNs), on 1, 64 and 257 rows (1 and 64 for ReActNet-A at
    224x224, whose float32 oracle on the host holds 205 MB a stream
    tensor at 64 rows);
  * batch-noise kernel votes against the last pass of the cumulative
    batch-noise spec (the XLA twin) given the same key;
  * every served result against a direct `run` of the same rows/keys.

Nothing is caught: a failed check or a refused compile exits non-zero.
Without a TPU the script exits non-zero before any work: there is no
CPU fallback.  The last line of standard output is one JSON object
naming the device.  The seconds printed are compile times, and the
server's figures come from a few small bursts: this is a smoke run,
not a measurement.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROWS = (1, 64, 257)  # one row, a full min bucket, a multi-block grid
# rows of the deployments whose host oracle would not hold 257
ORACLE_ROWS = {"reactnet_a_imagenet": (1, 64)}


def _require_tpu():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            "chip_smoke: no TPU found (jax.devices()[0].platform="
            f"{devices[0].platform!r}); there is no CPU fallback"
        )
    return devices


def _deployments():
    from repro.configs.paper_cnn import (BIREAL18_CIFAR10, HG_CNN,
                                         MNIST_CNN, REACTNET_A_IMAGENET)
    from repro.configs.paper_mlp import HG_MLP, MNIST_MLP

    return (("mnist_mlp", MNIST_MLP), ("hg_mlp", HG_MLP),
            ("mnist_cnn", MNIST_CNN), ("hg_cnn", HG_CNN),
            ("bireal18_cifar10", BIREAL18_CIFAR10),
            ("reactnet_a_imagenet", REACTNET_A_IMAGENET))


def _folded(cfg, seed: int):
    from repro.core import bnn, convnet

    if isinstance(cfg, convnet.CNNConfig):
        return convnet.random_folded_cnn(cfg, seed=seed)
    return bnn.random_folded(cfg, seed=seed)


def _deploy(cfg, folded, **kw):
    from repro.configs.paper_cnn import deploy_cnn
    from repro.configs.paper_mlp import deploy_mlp
    from repro.core.convnet import CNNConfig

    if isinstance(cfg, CNNConfig):
        return deploy_cnn(cfg, folded, **kw)
    return deploy_mlp(cfg, folded, **kw)


def _inputs(cfg, n: int, seed: int) -> np.ndarray:
    """Request rows: ±1 activations (MLP) or [0,1] pixels (CNN)."""
    from repro.core.convnet import CNNConfig

    rng = np.random.default_rng(seed)
    if isinstance(cfg, CNNConfig):
        return rng.random((n, cfg.n_in)).astype(np.float32)
    return rng.choice([-1.0, 1.0], (n, cfg.layer_sizes[0])).astype(
        np.float32
    )


def _oracle(cfg, folded, head, x) -> np.ndarray:
    """Unpacked float32 reference votes, computed on the host CPU."""
    import jax
    import jax.numpy as jnp

    from repro.core import bnn, ensemble
    from repro.core.convnet import CNNConfig
    from repro.kernels import ref

    with jax.default_device(jax.devices("cpu")[0]):
        if isinstance(cfg, CNNConfig):
            votes = ref.conv_votes_ref(folded, head, x, cfg.encoding,
                                       cfg.side, cfg.channels)
        else:
            y = bnn.folded_forward_exact(folded[:-1], jnp.asarray(x))
            votes = ensemble.votes_fused(head, jnp.where(y >= 0, 1.0, -1.0))
        return np.asarray(votes)


def _assert_kernel(pipe, spec, x, **keys) -> None:
    """Refuse to go on unless the program runs the chip's path: the
    fused Pallas kernel for an MLP, int8 convolutions for a CNN."""
    import jax

    text = jax.jit(lambda v: pipe.run(v, spec, **keys)).lower(x).as_text()
    if pipe.weight_operands:
        if "stablehlo.convolution" not in text or "xi8>" not in text:
            raise SystemExit(f"{spec.describe()} lowered without int8 "
                             "convolutions")
    elif "tpu_custom_call" not in text:
        raise SystemExit(
            f"{spec.describe()} lowered without a Mosaic kernel: the "
            "program would run the XLA twin or the interpreter"
        )


class _CompileClock:
    """Sums JAX's backend-compile seconds recorded while it is active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration

    def __enter__(self) -> "_CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def _equal(name: str, got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = (int((got != want).any(-1).sum())
               if got.shape == want.shape else "shape")
        raise SystemExit(f"{name}: mismatch ({bad} rows differ; got shape "
                         f"{got.shape}, want {want.shape})")
    return int(got.shape[0])


def pipelines_phase(seed: int) -> None:
    """Every deployment: kernel vs oracle, batch noise vs the XLA twin
    (the last pass of the cumulative batch-noise spec: the same draw)."""
    import jax

    from repro.core.device_model import SILICON
    from repro.spec import InferenceSpec

    spec_off, spec_batch = InferenceSpec(), InferenceSpec(noise="batch")
    spec_twin = InferenceSpec(noise="batch", cumulative=True)
    key = jax.random.PRNGKey(seed)
    for name, cfg in _deployments():
        folded = _folded(cfg, seed)
        pipe = _deploy(cfg, folded).pipeline()
        noisy = _deploy(cfg, folded, noise=SILICON).pipeline()
        rows = ORACLE_ROWS.get(name, ROWS)
        x = _inputs(cfg, max(rows), seed + 1)
        _assert_kernel(pipe, spec_off, x[:64])
        _assert_kernel(noisy, spec_batch, x[:64], key=key)
        want = _oracle(cfg, folded, pipe.head, x)
        compile_s, noise_s, n_exact, n_noise = {}, {}, 0, 0
        for n in rows:
            with _CompileClock() as clock:
                got = jax.block_until_ready(pipe.run(x[:n], spec_off))
            compile_s[n] = clock.seconds
            n_exact += _equal(f"{name} rows={n} vs oracle", got, want[:n])
            with _CompileClock() as clock:
                got = jax.block_until_ready(
                    noisy.run(x[:n], spec_batch, key=key)
                )
            noise_s[n] = clock.seconds
            ref_votes = noisy.run(x[:n], spec_twin, key=key)[-1]
            n_noise += _equal(f"{name} rows={n} batch noise vs xla twin",
                              got, ref_votes)
        perturbed = int((np.asarray(got) != want[:n]).any(-1).sum())
        path = ("int8 MXU program" if pipe.weight_operands
                else "pallas kernel")
        print(f"[pipeline] {name}: {path}, bit-exact vs oracle "
              f"{n_exact}/{n_exact} rows, batch noise == xla twin "
              f"{n_noise}/{n_noise} rows ({perturbed} of {n} rows moved "
              "by noise); first-call s (compile) by rows: noiseless "
              + ", ".join(f"{n}:{s:.2f}" for n, s in compile_s.items())
              + "; batch noise "
              + ", ".join(f"{n}:{s:.2f}" for n, s in noise_s.items()),
              flush=True)


def _serve(devices, models, bursts):
    """Serve `bursts` [(model_id, x, keys)] one burst at a time; return
    per-burst (votes, devices) and the server's stats."""
    from repro.serve.picbnn import BatchingPolicy, PicBnnServer

    out = []
    server = PicBnnServer(BatchingPolicy(max_batch=256, max_wait_us=500),
                          devices=devices)
    for model_id, dep in models.items():
        server.register(model_id, dep, warmup=True)
    with server:
        for model_id, x, keys in bursts:
            h = server.submit_many(model_id, x, keys)
            res = h.results(timeout=600)
            out.append((np.stack([r.votes for r in res]),
                        {r.device for r in res}))
    return out, server.stats()


def _serving_models(seed: int):
    from repro.configs.paper_mlp import MNIST_MLP
    from repro.core.device_model import SILICON

    folded = _folded(MNIST_MLP, seed)
    return MNIST_MLP, {
        "mnist_noiseless": _deploy(MNIST_MLP, folded),
        "mnist_silicon": _deploy(MNIST_MLP, folded, noise=SILICON),
    }


def _bursts(cfg, seed: int, n_bursts: int, sizes=(1, 7, 64, 40, 100)):
    import jax

    out = []
    for i in range(n_bursts):
        n = sizes[i % len(sizes)]
        x = _inputs(cfg, n, seed + 100 + i)
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed + i), n))
        out.append(("mnist_noiseless", x, None))
        out.append(("mnist_silicon", x, keys))
    return out


def serving_phase(seed: int) -> None:
    """MNIST MLP served noiseless and silicon; served == direct run."""
    import jax

    from repro.spec import InferenceSpec

    cfg, models = _serving_models(seed)
    bursts = _bursts(cfg, seed, 10)
    served, stats = _serve(jax.devices()[:1], models, bursts)
    n = 0
    for (model_id, x, keys), (votes, _) in zip(bursts, served):
        pipe = models[model_id].pipeline()
        if keys is None:
            want = pipe.run(x, InferenceSpec())
        else:
            want = pipe.run(x, InferenceSpec(noise="per_request"), keys=keys)
        n += _equal(f"served {model_id} vs direct run", votes, want)
    print(f"[serve] {n}/{n} served results == direct run "
          f"({len(bursts)} bursts, noiseless + silicon per-request keys)",
          flush=True)
    print("[serve] smoke-run stats (compile-warm, not a measurement): "
          + stats.summary().replace("\n", " | "), flush=True)


def four_chip_phase(seed: int, devices) -> None:
    """Round-robin over every device == the same requests on device 0."""
    cfg, models = _serving_models(seed)
    bursts = _bursts(cfg, seed, 4 * len(devices), sizes=(64, 40))
    served, stats = _serve(devices, models, bursts)
    alone, _ = _serve(devices[:1], models, bursts)
    used = set().union(*(d for _, d in served))
    if used != set(range(len(devices))):
        raise SystemExit(f"devices {sorted(used)} served batches; expected "
                         f"all of {list(range(len(devices)))}")
    n = 0
    for (model_id, _, _), (votes, _), (votes0, _) in zip(bursts, served,
                                                         alone):
        n += _equal(f"{model_id} on {len(devices)} devices vs device 0",
                    votes, votes0)
    print(f"[four-chips] {n}/{n} results bit-exact vs device 0 alone; "
          f"batches served on devices {sorted(used)} "
          f"({stats.n_batches} batches)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip round-robin serving check")
    args = ap.parse_args(argv)

    devices = _require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    if args.four_chips:
        if len(devices) != 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
        four_chip_phase(args.seed, devices)
    else:
        pipelines_phase(args.seed)
        serving_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
