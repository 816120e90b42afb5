"""Pallas TPU kernels for the paper's compute hot spots.

  binary_gemm — bit-packed XNOR-popcount GEMM (the CAM matchline array,
                adapted to VPU popcount over uint32 words)
  cam_search  — fused multi-threshold CAM vote (Algorithm 1 in one pass)
  fused_mlp   — the ENTIRE deployed BNN in one pass: packed matvec + bias
                + sign + shift-or repack per layer, vote at the head;
                hidden activations never leave VMEM (the served path)
  fused_conv  — the conv sibling: packed-domain binary convolution with
                im2col folded into the channel-packed layout (per-tap
                strided loads of the VMEM-resident feature map), then
                the fused_mlp FC/vote tail — the end-to-end-binary CNN
                workload in one pass (the served path)
  ops         — jit'd public wrappers (interpret-mode on CPU)
  ref         — pure-jnp oracles used by the test suite

Kernels are validated bit-exact in interpret mode on CPU.  The two served
kernels are also compiled by Mosaic for a described v5e chip in
tests/test_tpu_compile.py, and run bit-exact on the chip by
`chip_smoke.py`: the batch is their lane axis and packed words sit on
sublanes (DESIGN.md §4, §10).
"""

from repro.kernels import ops, ref  # noqa: F401
