"""Mean inclusive ms of the program's `pump.solve` span in the window:
one attempt's dispatch of the window program and its wait (program_span)."""
from benchmarks.harness.readers import span_ms


def read(ctx):
    return span_ms(ctx, "pump.solve")
