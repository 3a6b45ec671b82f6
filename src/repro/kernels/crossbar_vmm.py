"""Blocked differential-pair crossbar VMM kernel.

Simulates the analogue array read path as one fused TPU kernel:

    y = clip( (x @ (G+ - G-)) / scale, -v_clamp, +v_clamp )

Two storage modes:
  * float mode — conductances as float (carries programming noise);
  * quantised mode — uint8 level indices (the device's 6-bit states),
    dequantised on the fly inside the kernel ((idx_p - idx_m) * g_step —
    the G_min offsets cancel in the noise-free differential pair).  This
    is the memristive analogue of an int-quantised weight GEMM: 4x less
    weight traffic than f32, dequant fused into the MXU feed.

Optional per-read noise: ``read_noise`` > 0 perturbs each conductance
multiplicatively with a counter-derived Gaussian stream
(:mod:`repro.kernels.noise`) keyed on ``noise_seed`` and the element's
global (k, n) coordinates — deterministic, so the same seed reproduces
the same read bitwise.  In quantised mode the full conductances
``g_min + idx * g_step`` are reconstructed first, because the G_min
offsets only cancel when both halves of the pair are noise-free.

Classic (M/bm, N/bn, K/bk) blocked matmul: fp32 accumulator scratch in
VMEM, K as the innermost (sequential, revisiting) grid dim; the
differential subtraction, dequant, noise, rescale and clamp are all
fused so the pair never materialises in HBM.

Padding follows the masked-padding discipline of the fleet tiles
(:func:`pad_accumulator_neutral`): pad rows/columns must be
*accumulator-neutral*, i.e. contribute exactly zero partial sums in
every mode.  Zero-padding alone guarantees that for the noise-free
paths (0 - 0 = 0 in float mode, (0 - 0) * g_step = 0 in quantised
mode), but NOT for noisy quantised reads — a zero level index still
reconstructs to ``g_min`` and the pair's noise does not cancel — so the
kernel masks reconstructed conductances against the true (K, N) extent
before accumulating.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.noise import counter_normal, stuck_cell_masks
from repro.kernels.platform import default_interpret


def pad_accumulator_neutral(x: jax.Array, mult: int, axis: int) -> jax.Array:
    """Pad ``axis`` up to a multiple of ``mult`` with accumulator-neutral
    values (zeros).

    This is the same discipline the fused fleet tiles use
    (``fused_ode_mlp.pad_fleet_to_tile``): padding must never change what
    the kernel accumulates for real elements.  For the crossbar operands
    zero *values* are neutral in both storage modes — float conductances
    pad as G+ = G- = 0, and uint8 level indices pad as idx_p = idx_m = 0
    whose dequant ``(0 - 0) * g_step`` is exactly 0.  Reads that
    reconstruct absolute conductances (the noisy quantised path) must
    additionally mask by the true extent; the kernel does.
    """
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def conductance_f32(g: jax.Array) -> jax.Array:
    """Widen stored conductances (f32, or uint8 level indices) to f32
    in-kernel; Mosaic has no direct uint8 -> float cast, so integer
    levels go through int32."""
    if jnp.issubdtype(g.dtype, jnp.integer):
        g = g.astype(jnp.int32)
    return g.astype(jnp.float32)


def _kernel(x_ref, gp_ref, gm_ref, o_ref, acc_ref, *, nk: int, bk: int,
            bn: int, K: int, N: int, g_step: float | None,
            g_min: float, g_max: float, inv_scale: float,
            clamp: float | None, read_noise: float, noise_seed: int,
            stuck_rate: float, stuck_on_frac: float, fault_seed: int,
            salt_p: int, salt_m: int, drift: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gp = conductance_f32(gp_ref[...])
    gm = conductance_f32(gm_ref[...])
    stuck = stuck_rate > 0.0
    if g_step is not None and (read_noise > 0.0 or stuck):
        # Quantised storage: reconstruct the absolute conductances —
        # G_min offsets cancel only in the clean (noise- and fault-free)
        # pair; stuck overrides and read noise both act on absolutes.
        gp = g_min + gp * g_step
        gm = g_min + gm * g_step
    if stuck:
        # Stuck cells pin to G_on/G_off at their GLOBAL coordinates —
        # bitwise the mask core/faults.py applies at program time, so
        # in-kernel injection (zero extra HBM traffic: the mask is
        # counter-derived, never materialised) matches a baked program.
        row0 = pl.program_id(2) * bk
        col0 = pl.program_id(1) * bn
        for arr, salt in ((0, salt_p), (1, salt_m)):
            is_stuck, stuck_on = stuck_cell_masks(
                fault_seed, salt, (bk, bn), stuck_rate, stuck_on_frac,
                row0=row0, col0=col0, ncols=N)
            val = jnp.where(stuck_on, jnp.float32(g_max), jnp.float32(g_min))
            if arr == 0:
                gp = jnp.where(is_stuck, val, gp)
            else:
                gm = jnp.where(is_stuck, val, gm)
    if read_noise > 0.0:
        # One salt per (k-tile, n-tile, pair): the element iota inside
        # counter_normal then decorrelates within the tile, so the full
        # (K, N) stream is deterministic in noise_seed alone.
        salt = (pl.program_id(2) * (2 * 65536)
                + pl.program_id(1) * 2)
        gp = gp * (1.0 + read_noise * counter_normal(
            noise_seed, salt, (bk, bn)))
        gm = gm * (1.0 + read_noise * counter_normal(
            noise_seed, salt + 1, (bk, bn)))
    if read_noise > 0.0 or stuck:
        # Masked-padding discipline: reconstructed pads sit at ~g_min
        # (and stuck overrides would pin pad cells to real conductances)
        # — zero everything past the true (K, N) extent so pads stay
        # accumulator-neutral.
        kk = pl.program_id(2) * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bk, bn), 0)
        nn = pl.program_id(1) * bn + jax.lax.broadcasted_iota(
            jnp.int32, (bk, bn), 1)
        valid = (kk < K) & (nn < N)
        g = jnp.where(valid, gp - gm, 0.0)
    else:
        g = gp - gm
        if g_step is not None:      # quantised mode: dequant level indices
            g = g * g_step
    if drift != 1.0:
        # Read-disturb relaxation scales both halves of the pair equally,
        # so the differential scales by the same (static) factor.
        g = g * jnp.float32(drift)
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, g, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        y = acc_ref[...] * inv_scale
        if clamp is not None:
            y = jnp.clip(y, -clamp, clamp)
        o_ref[...] = y.astype(o_ref.dtype)


def crossbar_matmul(
    x: jax.Array,          # (M, K)
    gp: jax.Array,         # (K, N) float conductances or uint8 level indices
    gm: jax.Array,         # (K, N)
    *,
    inv_scale: float,
    g_step: float | None = None,   # set => quantised (uint8) mode
    clamp: float | None = None,
    read_noise: float = 0.0,
    noise_seed: int = 0,
    g_min: float = 0.0,            # needed for noisy quantised reconstruction
    g_max: float = 0.0,            # needed for stuck-cell overrides
    stuck_rate: float = 0.0,
    stuck_on_frac: float = 0.5,
    fault_seed: int = 0,
    fault_salts: tuple[int, int] = (0, 1),   # (G+ salt, G- salt)
    drift: float = 1.0,
    bm: int = 128, bk: int = 128, bn: int = 128,
    interpret: bool | None = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Fused differential-pair VMM.

    Pads every dim to its tile multiple (hardware 8x128 alignment) with
    accumulator-neutral values and slices the result back.
    ``interpret=None`` auto-detects the accelerator (compiled on TPU,
    interpreter elsewhere; ``REPRO_FORCE_INTERPRET`` pins the mode).
    ``read_noise`` > 0 applies the deterministic counter-derived read
    perturbation described in the module docstring.

    Device faults are injected in-kernel (counter-derived, zero extra
    HBM traffic — see :mod:`repro.core.faults` for the model and the
    salt convention): ``stuck_rate`` > 0 pins that fraction of cells to
    ``g_max``/``g_min`` at their global coordinates, bitwise-identical
    to program-time baking, and ``drift`` scales every conductance by a
    static read-disturb relaxation factor.
    """
    if interpret is None:
        interpret = default_interpret()
    M, K = x.shape
    K2, N = gp.shape
    assert K == K2 and gm.shape == gp.shape
    if read_noise > 0.0 and g_step is not None and g_min <= 0.0:
        raise ValueError(
            "crossbar_matmul: noisy quantised reads need the absolute "
            "conductance floor — pass g_min > 0 (spec.g_min)")
    if stuck_rate > 0.0 and not g_max > g_min:
        raise ValueError(
            "crossbar_matmul: stuck-cell injection pins cells to the "
            "absolute G_on/G_off values — pass g_max > g_min "
            "(spec.g_max/spec.g_min)")

    bm = min(bm, max(8, M))
    bn = min(bn, max(128, 128))
    bk = min(bk, max(128, 128))
    xp = pad_accumulator_neutral(
        pad_accumulator_neutral(x, bm, 0), bk, 1)
    gpp = pad_accumulator_neutral(
        pad_accumulator_neutral(gp, bk, 0), bn, 1)
    gmp = pad_accumulator_neutral(
        pad_accumulator_neutral(gm, bk, 0), bn, 1)
    Mp, Kp = xp.shape
    _, Np = gpp.shape
    nk = Kp // bk

    kernel = functools.partial(_kernel, nk=nk, bk=bk, bn=bn, K=K, N=N,
                               g_step=g_step, g_min=float(g_min),
                               g_max=float(g_max),
                               inv_scale=float(inv_scale), clamp=clamp,
                               read_noise=float(read_noise),
                               noise_seed=int(noise_seed),
                               stuck_rate=float(stuck_rate),
                               stuck_on_frac=float(stuck_on_frac),
                               fault_seed=int(fault_seed),
                               salt_p=int(fault_salts[0]),
                               salt_m=int(fault_salts[1]),
                               drift=float(drift))
    out = pl.pallas_call(
        kernel,
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, gpp, gmp)
    return out[:M, :N]
