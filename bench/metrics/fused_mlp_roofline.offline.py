"""fused_mlp_roofline.offline: the roofline's least time of the window's
`fused_mlp` work over the summed device time of its calls in the traced
window, in %.  Work counts the rows answered, not the padded rows
(`bench/peaks.py`)."""

from bench import peaks


def read(ctx):
    return peaks.kernel_roofline(ctx, "fused_mlp")
