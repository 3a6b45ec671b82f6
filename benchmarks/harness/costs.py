"""Operations and HBM bytes of each kernel call, from its shapes, and the
peak table they are held against.

The byte model of the fused kernels is a copy of ``benchmarks/run.py``'s
``_fused_hbm_bytes`` (y0 and the f32 drive slab in, weights in and the
trajectory slab out at the policy's storage width; the backward adds the
cotangent slab in and the f32 weight-gradient accumulators and dy0 out),
extended with the per-twin drive slab of driven fleets.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak figures for device kind {device_kind!r} in "
                       f"{PEAKS.name}; have {sorted(table)}")
    return table[device_kind]


def mlp_flops(sizes) -> int:
    """Multiply-adds of one MLP evaluation, counted as 2 operations each."""
    return 2 * sum(i * o for i, o in zip(sizes[:-1], sizes[1:]))


def rk4_step_flops(sizes) -> int:
    """One RK4 step evaluates the field four times."""
    return 4 * mlp_flops(sizes)


def _storage_bytes(precision: str) -> int:
    return 4 if precision == "f32" else 2


def weight_count(sizes) -> int:
    return sum(i * o + o for i, o in zip(sizes[:-1], sizes[1:]))


def fused_fwd_cost(sizes, *, steps: int, rows: int, precision: str,
                   per_twin_drive: bool = False) -> tuple[float, float]:
    """(operations, HBM bytes) of one forward ``fused_node_rollout`` over
    ``rows`` twins (after tile padding) and ``steps`` RK4 steps."""
    D = sizes[-1]
    du = sizes[0] - D
    sb = _storage_bytes(precision)
    drive_cols = max(du, 1) * (rows if per_twin_drive else 1)
    nbytes = (rows * D * 4 + (2 * steps + 1) * drive_cols * 4
              + weight_count(sizes) * sb + steps * rows * D * sb)
    return float(rows * steps * rk4_step_flops(sizes)), float(nbytes)


def fused_bwd_cost(sizes, *, steps: int, rows: int, precision: str,
                   per_twin_drive: bool = False) -> tuple[float, float]:
    """(operations, HBM bytes) of one reverse-time ``fused_node_rollout_bwd``
    call: the VJP of every matmul (input and weight cotangents, twice the
    forward's operations; the forward replay inside the kernel is not
    counted) and the forward's bytes plus the cotangent slab in and the
    f32 gradient accumulators and dy0 out."""
    D = sizes[-1]
    sb = _storage_bytes(precision)
    flops, nbytes = fused_fwd_cost(sizes, steps=steps, rows=rows,
                                   precision=precision,
                                   per_twin_drive=per_twin_drive)
    nbytes += steps * rows * D * sb + weight_count(sizes) * 4 + rows * D * 4
    return 2.0 * flops, float(nbytes)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time for a call and which bound sets it."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
