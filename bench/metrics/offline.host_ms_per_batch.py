"""offline.host_ms_per_batch: mean host time inside one
`Deployment.run` call (staging, pack and program enqueue), from the
benchmark's own span around each call (host clock)."""


def read(ctx):
    ms = ctx.window.get("host_ms")
    return float(ms.mean()) if ms is not None and len(ms) else None
