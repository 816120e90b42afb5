"""Pallas TPU kernels for the paper's compute hot spots.

  binary_gemm — bit-packed XNOR-popcount GEMM (the CAM matchline array,
                adapted to VPU popcount over uint32 words)
  cam_search  — fused multi-threshold CAM vote (Algorithm 1 in one pass)
  fused_mlp   — the ENTIRE deployed BNN in one pass: packed matvec + bias
                + sign + shift-or repack per layer, vote at the head;
                hidden activations never leave VMEM (the served path)
  fused_conv  — the deployed binary CNN as one XLA program of ±1 int8
                products on the MXU (padded, pooled convs, FC layers,
                head distances); weights are program arguments
  ops         — jit'd public wrappers (interpret-mode on CPU)
  ref         — pure-jnp oracles used by the test suite

Kernels are validated bit-exact in interpret mode on CPU.  `fused_mlp`
and the conv program are also compiled for a described v5e chip in
tests/test_tpu_compile.py, and run bit-exact on the chip by
`chip_smoke.py`.  In `fused_mlp` the batch is the lane axis and packed
words sit on sublanes (DESIGN.md §4); the conv program's layout is
DESIGN.md §10.
"""

from repro.kernels import ops, ref  # noqa: F401
