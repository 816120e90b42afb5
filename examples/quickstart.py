"""Quickstart: the paper's full pipeline in one script.

1. Generate a synthetic MNIST-like dataset (10 classes, 28x28).
2. Train the paper's binary MLP (784 -> 128 -> 10) with sign-STE + BN.
3. Fold batch-norm into integer constants C_j (Eq. 3).
4. Deploy to CAM arrays (bank tiling) and run Algorithm 1: 33 output-layer
   executions with swept HD tolerance, majority vote.
5. Report: software baseline vs end-to-end-binary accuracy, and the
   silicon performance model (Table II figures).

Run:  PYTHONPATH=src python examples/quickstart.py [--fast]
"""

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.core import bnn, ensemble, mapping
from repro.core.device_model import SILICON, knob_schedule
from repro.data.synthetic import MNIST_LIKE, binarize_images, make_dataset
from repro.deploy import Deployment, deploy
from repro.spec import InferenceSpec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    epochs = 3 if args.fast else 10
    n_train = 2000 if args.fast else 8000

    print("=== 1. synthetic MNIST-like dataset ===")
    tx, ty, vx, vy = make_dataset(MNIST_LIKE, n_train=n_train, n_test=1000)
    txb, vxb = binarize_images(tx), binarize_images(vx)
    print(f"train {txb.shape}, test {vxb.shape}, inputs binarized to +-1")

    print("=== 2. train binary MLP 784->128->10 (sign-STE + BN) ===")
    cfg = bnn.MLPConfig(layer_sizes=(784, 128, 10), bias_cells=64)
    t0 = time.time()
    params = bnn.train_mlp(
        jax.random.PRNGKey(0), cfg, txb, ty, epochs=epochs, batch=128,
        lr=2e-3, verbose=True,
    )
    print(f"trained in {time.time() - t0:.1f}s")
    sw = bnn.eval_accuracy(params, cfg, vxb, vy, topk=(1, 2))
    print(f"software baseline: top1={sw['top1']:.4f} top2={sw['top2']:.4f}")

    print("=== 3. fold BN into C_j (Eq. 3) ===")
    folded = bnn.fold(params, cfg)
    for i, f in enumerate(folded):
        print(f"layer {i}: W{f.weights_pm1.shape}, C_j in "
              f"[{f.c.min()}, {f.c.max()}]")

    print("=== 4. map to CAM banks ===")
    mapped = [mapping.map_layer(l, cfg.bias_cells) for l in folded[:-1]]
    for i, m in enumerate(mapped):
        print(f"layer {i}: plan {m.plan}")
    ecfg = ensemble.EnsembleConfig()
    head = ensemble.build_head(folded[-1], ecfg)
    knobs, achieved = knob_schedule(len(ecfg.thresholds), 64)
    print(f"output head: {head.n_classes} class rows, "
          f"{len(ecfg.thresholds)} passes; first knob settings "
          f"(V_ref,V_eval,V_st)={knobs[0].round(3).tolist()} -> HD "
          f"{achieved[0]:.1f}")

    print("=== 5. Algorithm 1 inference (deployment + InferenceSpec) ===")
    # deployment artifact: folded layers + ensemble config bundled; the
    # fused packed-domain pipeline (all layers + the 33-threshold vote in
    # one compiled program) compiles lazily per request spec
    dep = deploy(folded, config=cfg, ens_cfg=ecfg)
    t0 = time.time()
    pred = dep.run(jnp.asarray(vxb), InferenceSpec(reduction="argmax"))
    acc = float((pred == jnp.asarray(vy)).mean())
    dt = time.time() - t0
    print(f"  end-to-end-binary top1 [fused pipeline]: "
          f"{acc:.4f}  ({len(vy) / dt / 1e3:.1f}K inf/s incl. compile)")
    # silicon PVT noise: the SAME fused program family, device physics
    # threaded through — a spec field selects the draw, the LLN claim is
    # 33 noisy passes ~ noiseless accuracy
    dep_si = deploy(folded, config=cfg, ens_cfg=ecfg, noise=SILICON)
    pred_si = dep_si.run(
        jnp.asarray(vxb),
        InferenceSpec(noise="batch", reduction="argmax"),
        key=jax.random.PRNGKey(7),
    )
    acc_si = float((pred_si == jnp.asarray(vy)).mean())
    print(f"  end-to-end-binary top1 [silicon PVT noise, fused]: "
          f"{acc_si:.4f}  (delta vs noiseless {100 * (acc - acc_si):+.2f} "
          f"points — LLN over {ecfg.n_passes} passes)")

    print("=== 6. silicon performance model (Table II) ===")
    plans = [m.plan for m in mapped] + [
        mapping.plan_layer(10, 128, cfg.bias_cells)
    ]
    cost = mapping.model_inference_cost(plans, len(ecfg.thresholds))
    print(f"  {cost.cycles} cycles/inference @25MHz -> "
          f"{cost.inferences_per_s/1e3:.0f}K inf/s "
          f"(paper: 560K); {1.0/cost.energy_j/1e6:.0f}M inf/s/W "
          f"(paper: 703M)")

    print("=== 7. serving: register deployments, even from disk ===")
    # both deployments behind one submit() API; silicon requests carry a
    # per-request PRNG key, so served draws are reproducible bit-for-bit.
    # The noiseless model round-trips through Deployment.save/load — the
    # path a production server takes when registering models from a
    # checkpoint directory.
    from repro.serve.picbnn import BatchingPolicy, PicBnnServer

    srv = PicBnnServer(BatchingPolicy(max_batch=256, max_wait_us=500.0))
    with tempfile.TemporaryDirectory() as ckpt_dir:
        dep.save(ckpt_dir)  # manifest + bit-packed weights
        srv.register("mnist", Deployment.load(ckpt_dir))
        srv.register("mnist-si", dep_si)
        srv.warmup()  # precompile every bucket: no first-request spike
        with srv:
            handles = [srv.submit("mnist", vxb[i]) for i in range(512)]
            h_si = srv.submit("mnist-si", vxb[0],
                              key=jax.random.PRNGKey(7))
            served = [h.wait() for h in handles]
            print(f"  served pred[0]={served[0]} (direct: {int(pred[0])}"
                  f"), silicon pred[0]={h_si.wait()}")
    print("  " + srv.stats().summary().replace("\n", "\n  "))

    print("=== 8. end-to-end-binary CNN workload ===")
    # the input layer is binary too: raw [0,1] pixels pass through a
    # thermometer encoding INSIDE the compiled program (the paper's
    # end-to-end claim, conv edition — see DESIGN.md §10)
    from repro.configs.paper_cnn import MNIST_CNN, deploy_cnn
    from repro.core import convnet

    cnn_epochs = 2 if args.fast else 6
    cnn_params = convnet.train_cnn(
        jax.random.PRNGKey(1), MNIST_CNN, tx, ty, epochs=cnn_epochs
    )
    # trained params + config in, deployment out (the fold runs inside)
    cnn_dep = deploy_cnn(MNIST_CNN, cnn_params)
    acc_sw = convnet.eval_cnn_accuracy(cnn_params, MNIST_CNN, vx, vy)["top1"]
    acc_cnn = float((cnn_dep.run(jnp.asarray(vx),
                                 InferenceSpec(reduction="argmax"))
                     == jnp.asarray(vy)).mean())
    si = convnet.cnn_inference_cost(MNIST_CNN).inferences_per_s
    print(f"  conv(3x3x32,s2) x2 -> FC128 -> 10-row CAM head, "
          f"thermometer-8 input")
    print(f"  software top1 {acc_sw:.4f} vs deployed Algorithm-1 "
          f"{acc_cnn:.4f}; silicon equivalent {si/1e3:.1f}K inf/s")
    cnn_srv = PicBnnServer(BatchingPolicy(max_batch=128, max_wait_us=500.0))
    cnn_srv.register("cnn-mnist", cnn_dep,
                     silicon_cost=convnet.cnn_inference_cost(MNIST_CNN))
    with cnn_srv:
        h = cnn_srv.submit("cnn-mnist", vx[0])  # raw [0,1] pixels
        direct = int(cnn_dep.run(vx[:1],
                                 InferenceSpec(reduction="argmax"))[0])
        print(f"  served CNN pred[0]={h.wait()} (direct: {direct})")


if __name__ == "__main__":
    main()
