"""MB of per-twin drive window the host assembles a pump in the window:
the program's StreamStats.drive_bytes over its batches (program_counter)."""
from benchmarks.harness.readers import counter


def read(ctx):
    nbytes, batches = counter(ctx, "stream.drive_bytes"), counter(
        ctx, "stream.batches")
    if nbytes is None or not batches:
        return None
    return nbytes / batches / 1e6
