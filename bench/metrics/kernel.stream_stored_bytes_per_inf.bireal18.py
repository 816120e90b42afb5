"""kernel.stream_stored_bytes_per_inf.bireal18: bytes the dispatched vote
programs store for the residual stream, each layer at its own width
(int16 where its proven bound fits, else int32), per row they answered,
from the program's counters `kernel.stream_stored_bytes` and
`kernel.rows` (`repro.obs`).  They run from process start, so they hold
the set-up's warm-up call beside the window's calls; every call of the
offline cell fills its bucket, so the stream's rows are the rows
answered.  Read in the traced run, as every per-layer metric; None where
the program keeps no such counter."""


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    if "kernel.stream_stored_bytes" not in c or not c.get("kernel.rows"):
        return None
    return c["kernel.stream_stored_bytes"] / c["kernel.rows"]
