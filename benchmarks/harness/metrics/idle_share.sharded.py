"""Device idle share of the traced window, mean over chips (device_trace)."""
from benchmarks.harness.readers import idle_share


def read(ctx):
    return idle_share(ctx)
