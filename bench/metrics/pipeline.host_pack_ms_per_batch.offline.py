"""pipeline.host_pack_ms_per_batch.offline: mean host time of one host
pack (`CompiledPipeline._pack_input` packing a host ±1 batch into uint32
words before staging it), from the program's counters `pack.host_ns`
and `pack.host_calls`.  They run from process start, so they hold the
set-up's warm-up call beside the window's calls.  Read in the traced
run, as every per-layer metric; None where the program keeps no such
counters (a program that packs every input on the device)."""


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    return c["pack.host_ns"] / c["pack.host_calls"] * 1e-6 \
        if c.get("pack.host_calls") else None
