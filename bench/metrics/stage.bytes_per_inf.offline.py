"""stage.bytes_per_inf.offline: bytes the program copied from host to
device per row it staged, from its counters `stage.bytes` and
`stage.rows` (`repro.obs`).  They run from process start, so they hold
the set-up's warm-up call beside the window's calls; every call of the
offline cell stages rows of one width.  Read in the traced run, as every
per-layer metric; None where the program keeps no such counters."""


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    return c["stage.bytes"] / c["stage.rows"] if c.get("stage.rows") else None
