"""step.mfu.offline: model operations per inference x inferences
completed per second of the window, over chips x the int8 peak, in %."""

from bench import peaks


def read(ctx):
    return peaks.step_mfu_rate(ctx)
