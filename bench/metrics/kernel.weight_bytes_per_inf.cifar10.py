"""kernel.weight_bytes_per_inf.cifar10: weight bytes the dispatched vote
programs read from HBM, as the program lays them out, per row they
answered, from the program's counters `kernel.weight_bytes` and
`kernel.rows` (`repro.obs`).  They run from process start, so they hold
the set-up's warm-up call beside the window's calls; every call of the
offline cell has one batch size.  Read in the traced run, as every
per-layer metric; None where the program keeps no such counters."""


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    return c["kernel.weight_bytes"] / c["kernel.rows"] \
        if c.get("kernel.rows") else None
