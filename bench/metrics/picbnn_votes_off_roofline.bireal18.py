"""picbnn_votes_off_roofline.bireal18: the roofline's least time of the
window's work of the vote program `jit_picbnn_votes_off` over the device
time of that program in the traced window, in %.  The binary ResNet
runs as that one XLA program (int8 convolutions on the MXU, the int32
residual stream between them), so no Pallas kernel call measures it.
Its trace holds more distinct fusions than the ten top ops the trace
keeps (about a fifth of its time falls outside them on one TPU v5e), so
the program's time is the device's busy time less the top ops of any
other program.  The work is the rows answered, not padded rows, with the
configuration's operations and bytes (`bench/configs/bireal18_cifar10.py`:
the stream is not counted as work); the weights are read once per call.
None where the trace's top ops hold no op of that program."""

from bench import peaks

PROGRAM = "jit_picbnn_votes_off/"


def read(ctx):
    if ctx.trace is None:
        return None
    top = ctx.trace["top_ops"]
    if not any(name.startswith(PROGRAM) for name, _ in top):
        return None
    busy = ctx.trace["busy_s"] - sum(s for name, s in top
                                     if not name.startswith(PROGRAM))
    rows = ctx.window["answered"]
    calls = rows // ctx.mix["batch"]
    least, _ = peaks.least_time_s(ctx.model.kernel_ops(rows),
                                  ctx.model.kernel_bytes(rows, calls),
                                  ctx.device_kind)
    return 100.0 * least / busy
