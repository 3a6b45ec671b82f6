"""Mean inclusive ms of the program's `pump.fetch` span in the window:
the state store's promotions, evictions and page-ins (program_span)."""
from benchmarks.harness.readers import span_ms


def read(ctx):
    return span_ms(ctx, "pump.fetch")
