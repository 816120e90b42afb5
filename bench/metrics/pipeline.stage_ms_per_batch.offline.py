"""pipeline.stage_ms_per_batch.offline: mean host time of one staging
call (`repro.obs.stage`: the host-to-device copy a `Deployment.run` call
starts), from the program's counters `stage.ns` and `stage.calls`.
They run from process start, so they hold the set-up's warm-up call
beside the window's calls.  Read in the traced run, as every per-layer
metric; None where the program keeps no such counters."""


def read(ctx):
    if ctx.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    return c["stage.ns"] / c["stage.calls"] * 1e-6 if c.get("stage.calls") \
        else None
