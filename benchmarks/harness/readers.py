"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader returns None where its cell's run gave it nothing to read; a
share of a roofline or a peak is then left out, never reported as 0.
"""
from __future__ import annotations

from . import costs, trace


def idle_share(ctx):
    tr = ctx.get("trace")
    return None if tr is None else trace.idle_share(tr)


def kernel_matcher(kernel: str):
    """Whether a device op is ``kernel`` (``trace.kernel_rules``)."""
    rule = trace.kernel_rules()[kernel]
    return lambda name, module: bool(rule.search(name))


def roofline(ctx, kernel: str):
    """Least time of the kernel's calls at the chip's peaks over their
    summed device time, in %."""
    tr, cost = ctx.get("trace"), ctx.get("kernels", {}).get(kernel)
    if tr is None or cost is None:
        return None
    events = trace.op_events(tr, kernel_matcher(kernel))
    spent = sum(d for _, d in events) * 1e-9
    if not events or spent <= 0:
        return None
    least, _ = costs.least_time_s(cost[0], cost[1], ctx["peak"])
    return 100.0 * len(events) * least / spent


def mfu(ctx, rate: str, flops: str):
    """Useful operations per second over the used chips' bf16 peak, in %."""
    if ctx.get(rate) is None or ctx.get(flops) is None:
        return None
    return (100.0 * ctx[rate] * ctx[flops]
            / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]))


def span_ms(ctx, name: str):
    """Mean inclusive milliseconds of the host span ``name`` over its
    occurrences that start inside the traced window; None where it never
    opened there."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    lo, hi = tr["window"]
    durs = [d for n, s, d in tr["spans"] if n == name and lo <= s < hi]
    return 1e-6 * sum(durs) / len(durs) if durs else None


def counter(ctx, name: str):
    """The window's change of the program counter ``"<group>.<field>"``
    (``cells.counter_deltas``), or None where the program has none."""
    return (ctx.get("counters") or {}).get(name)
