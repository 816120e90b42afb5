"""setup_s: process start to the first measured request (host clock):
imports, weights, deployment, compiling or loading every program the
mix uses, and the warm-up traffic."""


def read(ctx):
    return ctx.setup_s
