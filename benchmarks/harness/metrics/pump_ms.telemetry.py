"""Mean wall time of the harness's span around each public pump() call in
the window (program_span)."""


def read(ctx):
    return ctx.get("pump_ms")
