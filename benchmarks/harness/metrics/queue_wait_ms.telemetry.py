"""Mean ms a request waited from its arrival to its first assembly into
a batch, over the requests started in the window (program_counter:
StreamStats.queue_wait_s and started)."""
from benchmarks.harness.readers import counter


def read(ctx):
    wait, started = counter(ctx, "stream.queue_wait_s"), counter(
        ctx, "stream.started")
    if wait is None or not started:
        return None
    return 1e3 * wait / started
