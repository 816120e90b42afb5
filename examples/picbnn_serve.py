"""PiC-BNN LM head serving demo (deliverable b).

Serves musicgen-medium (reduced) through the decode path TWICE over the
same binary CAM match:
  1. "exact" readout — full-precision POPCOUNT per class (what an
     ADC/TDC-based processing-in-memory design reads out; the paper's
     competitor baseline),
  2. "votes" readout — PiC-BNN Algorithm 1: purely binary measurements
     across the threshold sweep, majority ranking, no ADC.

Reports the greedy-decode agreement between the two readouts — the
LM-scale version of the paper's "binary votes recover the argmax" claim —
plus the HBM-traffic saving of the bit-packed head.

Run:  PYTHONPATH=src python examples/picbnn_serve.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.compile_cache import use_compile_cache
from repro.models import model as M


def main():
    use_compile_cache()
    rng = np.random.default_rng(0)
    cfg_votes = configs.get_config("musicgen-medium+smoke+cam-head")
    cfg_exact = configs.get_config("musicgen-medium+smoke+cam-head-exact")

    # identical weights for both readouts (same init key/structure)
    params = M.init_params(cfg_votes, jax.random.PRNGKey(0))

    b, s, steps = 4, 12, 16
    embeds = jnp.asarray(
        rng.normal(0, 1, (b, s, cfg_votes.d_model)).astype(np.float32)
    )
    frames = [
        jnp.asarray(
            np.random.default_rng(100 + t)
            .normal(0, 1, (b, 1, cfg_votes.d_model))
            .astype(np.float32)
        )
        for t in range(steps - 1)
    ]

    streams = {}
    for name, cfg in [("adc-exact-readout", cfg_exact),
                      ("picbnn-votes", cfg_votes)]:
        logits, cache = M.prefill(params, cfg, embeds=embeds,
                                  max_len=s + steps)
        toks = [np.argmax(np.asarray(logits), -1)]
        for t, nxt in enumerate(frames):
            lg, cache = M.decode(params, cfg, cache, nxt, jnp.int32(s + t))
            toks.append(np.argmax(np.asarray(lg), -1))
        streams[name] = np.stack(toks, 1)  # [B, steps]
        print(f"[{name}] first stream: {streams[name][0][:10].tolist()}")

    agree = (streams["adc-exact-readout"] == streams["picbnn-votes"]).mean()
    print(f"\ngreedy-decode agreement, ADC readout vs PiC-BNN votes: "
          f"{agree:.3f}")
    print("(every disagreement is a vote tie from the threshold-sweep "
          "quantization — the paper's precision/efficiency trade)")

    # Fig. 5 at LM scale: agreement grows with the pass count, exactly as
    # the paper's accuracy grows with output-layer executions — but at
    # 2048 classes the required pass count is larger than the paper's 33.
    import dataclasses
    from repro.models import binary_lm

    print("\npass-count sweep (Fig. 5 analogue, 2048-way codebook):")
    rng3 = np.random.default_rng(5)
    h = jnp.asarray(rng3.normal(0, 1, (256, cfg_votes.d_model))
                    .astype(np.float32))
    for n_pass in (9, 17, 33, 65, 129):
        c = dataclasses.replace(cfg_votes, cam_head_thresholds=n_pass)
        ph = binary_lm.init_cam_head(c, jax.random.PRNGKey(0))
        votes = np.asarray(binary_lm.cam_head_logits(ph, c, h))
        exact = np.asarray(binary_lm.cam_head_logits(
            ph, dataclasses.replace(c, cam_head_mode="exact"), h))
        a = (votes.argmax(-1) == exact.argmax(-1)).mean()
        print(f"  {n_pass:4d} passes: argmax agreement {a:.3f}")

    d, v = cfg_votes.d_model, cfg_votes.vocab_size
    dense_bytes = d * v * 2  # bf16 head read per token
    cam_bytes = d * v // 8  # bit-packed rows
    print(f"\nLM-head HBM traffic per decoded token: dense bf16 "
          f"{dense_bytes/1e6:.2f} MB vs packed CAM {cam_bytes/1e6:.3f} MB "
          f"({dense_bytes//cam_bytes}x less); prefill logits also skip "
          f"the vocab matmul's f32 accumulation")


if __name__ == "__main__":
    main()
