"""Served twin-steps/s times RK4-MLP operations per twin-step, over the
chips' bf16 peak (host_clock)."""
from benchmarks.harness.readers import mfu


def read(ctx):
    return mfu(ctx, "twin_steps_per_s", "flops_per_twin_step")
