"""picbnn_votes_off_roofline.cifar10: the roofline's least time of the
window's work of the vote program `jit_picbnn_votes_off` over the device
time of that program's ops in the traced window, in %.  The conv net
runs as that one XLA program (int8 convolutions on the MXU), so its ops
are read from the trace's top ops, "<program>/<op>", and not from the
Pallas kernel calls.  The work is the rows answered, not padded rows,
with the configuration's operations and bytes (`bench/configs/
binarynet_cifar10.py`); the weights are read once per call.  None where
the trace holds no op of that program."""

from bench import peaks

PROGRAM = "jit_picbnn_votes_off/"


def read(ctx):
    if ctx.trace is None:
        return None
    busy = sum(s for name, s in ctx.trace["top_ops"]
               if name.startswith(PROGRAM))
    if not busy:
        return None
    rows = ctx.window["answered"]
    calls = rows // ctx.mix["batch"]
    least, _ = peaks.least_time_s(ctx.model.kernel_ops(rows),
                                  ctx.model.kernel_bytes(rows, calls),
                                  ctx.device_kind)
    return 100.0 * least / busy
