"""Mean inclusive ms of the program's `pump.commit` span in the window:
the trajectory copy-out, the stitch of each window and the state write
(program_span)."""
from benchmarks.harness.readers import span_ms


def read(ctx):
    return span_ms(ctx, "pump.commit")
