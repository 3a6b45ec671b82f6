"""Mean inclusive ms of the program's `solve.drive` span in the window:
the host's assembly of the per-twin drive window (program_span)."""
from benchmarks.harness.readers import span_ms


def read(ctx):
    return span_ms(ctx, "solve.drive")
