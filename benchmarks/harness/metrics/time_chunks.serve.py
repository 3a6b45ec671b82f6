"""Time chunks of the fused kernel a pump in the window: the program's
StreamStats.time_chunks over its batches; a window longer than one VMEM
chunk streams through several (program_counter)."""
from benchmarks.harness.readers import counter


def read(ctx):
    chunks, batches = counter(ctx, "stream.time_chunks"), counter(
        ctx, "stream.batches")
    if chunks is None or not batches:
        return None
    return chunks / batches
