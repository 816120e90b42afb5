"""device.idle_share.offline: 1 - busy / window of the traced window, where
busy is the union of the device's op intervals, averaged over the
cell's chips, in %."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace["idle_share"]
