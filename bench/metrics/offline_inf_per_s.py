"""offline_inf_per_s: rows classified and read back to the host inside
the window, over the window's length (host clock)."""


def read(ctx):
    return ctx.window["completed_in_window"] / ctx.window["seconds"]
