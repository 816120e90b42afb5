"""Peak rates of each accelerator, keyed by `device_kind`, and the roofline.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A binary multiply-
accumulate is counted as two int8 operations.  A kind that is not in
the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    """The peak table row of `device_kind`; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(ops: float, nbytes: float, device_kind: str) -> tuple:
    """(seconds, bound): the roofline's least time and which bound sets it."""
    p = peak(device_kind)
    t_ops = ops / p["int8_ops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def kernel_roofline(ctx, kernel: str):
    """Share (%) of the roofline that `kernel`'s calls in the traced
    window reach: the least time of the window's kernel work over the
    summed device time of its calls.  The work is the rows the window
    answered, not the padded rows each call computes, so padding counts
    as time and not as work; the weights are read once per call.  None
    when the cell's model does not run that kernel or the trace has no
    call of it."""
    if ctx.trace is None or ctx.model.kernel != kernel:
        return None
    calls = ctx.trace["kernels"].get(kernel)
    if not calls:
        return None
    rows = ctx.window["answered"]
    least, _ = least_time_s(ctx.model.kernel_ops(rows),
                            ctx.model.kernel_bytes(rows, len(calls)),
                            ctx.device_kind)
    return 100.0 * least / sum(s for _, s in calls)


def step_mfu_rate(ctx):
    """Model operations per inference x inferences completed per second
    of the window, over chips x the int8 peak (%)."""
    rate = ctx.window["completed_in_window"] / ctx.window["seconds"]
    peak_ops = ctx.chips * peak(ctx.device_kind)["int8_ops_per_s"]
    return 100.0 * ctx.model.ops_per_row * rate / peak_ops
