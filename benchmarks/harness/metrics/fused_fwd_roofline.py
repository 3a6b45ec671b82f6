"""The forward fused RK4-MLP kernel's share of its roofline (device_trace)."""
from benchmarks.harness.readers import roofline


def read(ctx):
    return roofline(ctx, "fused_fwd")
