"""Blocking device->host reads a pump in the window: the serving loop's
(StreamStats.host_syncs) and the state store's eviction reads
(StoreStats.evict_reads), over the batches served (program_counter)."""
from benchmarks.harness.readers import counter


def read(ctx):
    syncs = counter(ctx, "stream.host_syncs")
    evict = counter(ctx, "store.evict_reads")
    batches = counter(ctx, "stream.batches")
    if syncs is None or evict is None or not batches:
        return None
    return (syncs + evict) / batches
