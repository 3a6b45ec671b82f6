"""Served twin-steps/s times RK4-MLP operations per twin-step, over the
bf16 peak of every chip of the twin mesh (host_clock)."""
from benchmarks.harness.readers import mfu


def read(ctx):
    return mfu(ctx, "twin_steps_per_s", "flops_per_twin_step")
