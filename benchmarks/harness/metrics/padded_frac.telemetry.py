"""Padded steps over real plus padded steps in the window, from the
server's StreamStats (program_counter)."""


def read(ctx):
    return ctx.get("padded_frac")
