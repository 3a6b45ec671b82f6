"""fit() steps/s times forward-plus-backward operations per step (no
recompute), over the chip's bf16 peak (host_clock)."""
from benchmarks.harness.readers import mfu


def read(ctx):
    return mfu(ctx, "fit_steps_per_s", "flops_per_step")
