"""Weights-stationary fused *analogue* neural-ODE solve.

The jnp crossbar simulator (:mod:`repro.core.analogue`) pays a full XLA
dispatch per RK4 stage — 4 stages x 3 layers x 2 differential dots per
step — which makes the paper's centrepiece substrate the slowest backend
in the repo.  This kernel closes that gap by running the ENTIRE analogue
trajectory inside one ``pallas_call`` with the crossbar semantics traced
in-kernel, reusing the weights-stationary, time-chunked architecture of
:mod:`repro.kernels.fused_ode_mlp` (same grid, same carry scratch, same
chunked drive slabs):

* conductance residency — the per-layer differential pairs (G+, G-) are
  the kernel's stationary operands, float32 conductances or uint8 6-bit
  level indices with dequant fused into the MXU feed;
* differential-pair read — each layer evaluates
  ``(x_aug @ G+ - x_aug @ G-) / scale`` with the bias folded as the
  constant-1 row (the crossbar idiom), per-tensor ``scale`` arriving as
  a traced (L,) operand (scales are data: programming runs under jit);
* peripheral clamp — optional output voltage clamp per layer
  (``v_clamp``), applied after rescaling exactly like
  ``analogue_matmul``;
* deterministic read noise — ``read_noise > 0`` perturbs every
  conductance per evaluation from the counter-derived stream of
  :mod:`repro.kernels.noise`, salted by (global step, RK4 stage, layer,
  pair): the noisy rollout is bitwise-replayable from ``noise_seed``
  alone, with no RNG state carried across chunks.

Noise-free fast path: the pair is combined ONCE per grid cell into
effective weights ``W_l = (G+ - G-)[:K] / scale_l`` (uint8 indices
dequantised through ``g_step``), so the steady-state inner loop runs a
single dot per layer — the same arithmetic as the digital fused kernel,
matching the jnp simulator to float32 rounding.  With read noise the
pair must stay separate (the perturbation does not cancel) and each
evaluation re-noises the stationary conductances in VMEM.

The result is inference-only by construction — the analogue substrate
is not differentiable (the paper trains digitally, then deploys) — and
always float32: conductances are physical quantities, not policy-typed
tensors.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET,
                                         chunked_drive_operand,
                                         plan_time_chunk)
from repro.kernels.crossbar_vmm import conductance_f32
from repro.kernels.noise import counter_normal, stuck_cell_masks
from repro.kernels.platform import default_interpret

#: Static fault parameters the kernel understands (subset optional);
#: produced by ``FaultModel.kernel_args()`` in :mod:`repro.core.faults`.
_FAULT_DEFAULTS = {
    "stuck_rate": 0.0, "stuck_on_frac": 0.5, "fault_seed": 0,
    "salt_base": 0, "drift_nu": 0.0, "drift_tau": 1.0, "drift_n0": 0,
}


def _make_kernel(num_layers: int, C: int, dt: float, drive_dim: int,
                 bt: int, per_tile_drive: bool, g_step: float | None,
                 g_min: float, g_max: float, v_clamp: float | None,
                 read_noise: float, noise_seed: int, stuck_rate: float,
                 stuck_on_frac: float, fault_seed: int, salt_base: int,
                 drift_nu: float, drift_tau: float, drift_n0: int,
                 step_offset: int = 0):
    stuck = stuck_rate > 0.0

    def apply_stuck(g, li, pair):
        # Stationary arrays are whole (unblocked), so local coordinates
        # ARE the global cell ids — the mask matches program-time baking
        # (core/faults.py) bitwise, derived from the counter stream with
        # zero extra HBM traffic.
        is_stuck, stuck_on = stuck_cell_masks(
            fault_seed, salt_base + 2 * li + pair, g.shape, stuck_rate,
            stuck_on_frac)
        val = jnp.where(stuck_on, jnp.float32(g_max), jnp.float32(g_min))
        return jnp.where(is_stuck, val, g)

    def kernel(*refs):
        y0_ref = refs[0]
        u_ref = refs[1]
        gp_refs = refs[2:2 + num_layers]
        gm_refs = refs[2 + num_layers:2 + 2 * num_layers]
        scale_ref = refs[2 + 2 * num_layers]
        out_ref = refs[3 + 2 * num_layers]
        carry_ref = refs[4 + 2 * num_layers]

        @pl.when(pl.program_id(1) == 0)
        def _():
            carry_ref[...] = y0_ref[...]

        inv_scales = [1.0 / scale_ref[li] for li in range(num_layers)]
        if read_noise > 0.0:
            # Stationary absolute conductances; re-noised per evaluation.
            if g_step is not None:
                gps = [g_min + conductance_f32(r[...]) * g_step
                       for r in gp_refs]
                gms = [g_min + conductance_f32(r[...]) * g_step
                       for r in gm_refs]
            else:
                gps = [conductance_f32(r[...]) for r in gp_refs]
                gms = [conductance_f32(r[...]) for r in gm_refs]
            if stuck:
                gps = [apply_stuck(g, li, 0) for li, g in enumerate(gps)]
                gms = [apply_stuck(g, li, 1) for li, g in enumerate(gms)]
        else:
            # Noise-free fast path: combine the pair once per cell.  The
            # G_min offsets cancel exactly (quantised) / by construction
            # (float), so the inner loop is a single dot per layer.
            # Stuck cells pin to ABSOLUTE conductances, so with faults
            # active the quantised pair must be reconstructed first.
            ws, bs = [], []
            for li in range(num_layers):
                gp_a = conductance_f32(gp_refs[li][...])
                gm_a = conductance_f32(gm_refs[li][...])
                if stuck:
                    if g_step is not None:
                        gp_a = g_min + gp_a * g_step
                        gm_a = g_min + gm_a * g_step
                    g = apply_stuck(gp_a, li, 0) - apply_stuck(gm_a, li, 1)
                else:
                    g = gp_a - gm_a
                    if g_step is not None:
                        g = g * g_step
                g = g * inv_scales[li]
                ws.append(g[:-1])        # (K, N) weight rows
                bs.append(g[-1:])        # (1, N): the constant-1 bias row
        salts_per_step = 4 * num_layers * 2     # stages x layers x pair
        # Hoisted out of the fori_loop body: program_id has no lowering
        # inside a captured loop jaxpr on the interpreter path.
        # ``step_offset`` shifts the GLOBAL step index: a rollout resumed
        # at step k with step_offset=k replays the same noise salts and
        # drift exponents the uninterrupted rollout would have used.
        chunk_step0 = step_offset + pl.program_id(1) * C

        def layer_out(x, li, salt, dfac):
            """One crossbar read: differential dot, rescale, clamp."""
            if read_noise > 0.0:
                shape = gps[li].shape
                ep = counter_normal(noise_seed, salt, shape)
                em = counter_normal(noise_seed, salt + 1, shape)
                g = (gps[li] * (1.0 + read_noise * ep)
                     - gms[li] * (1.0 + read_noise * em))
                y = (jnp.dot(x, g[:-1], preferred_element_type=jnp.float32)
                     + g[-1:]) * inv_scales[li]
            else:
                y = jnp.dot(x, ws[li],
                            preferred_element_type=jnp.float32) + bs[li]
            if dfac is not None:
                # drift scales every conductance of the pair, hence the
                # whole differential read (bias row included)
                y = y * dfac
            if v_clamp is not None:
                y = jnp.clip(y, -v_clamp, v_clamp)
            return y

        def f(u_row, y, eval_salt, dfac):
            if drive_dim > 0:
                u = (u_row if per_tile_drive
                     else jnp.broadcast_to(u_row, (bt, drive_dim)))
                x = jnp.concatenate([u.astype(jnp.float32), y], axis=-1)
            else:
                x = y
            for li in range(num_layers):
                x = layer_out(x, li, eval_salt + 2 * li, dfac)
                if li < num_layers - 1:
                    x = jnp.maximum(x, 0.0)
            return x

        def body(t, y):
            # Global step index -> unique salt block per (step, stage).
            step_salt = ((chunk_step0 + t) * salts_per_step
                         if read_noise > 0.0 else 0)
            if drift_nu > 0.0:
                # Live read-disturb relaxation: every RK4 step costs 4
                # reads of each array, so the decay exponent advances
                # with the GLOBAL step count — chunked rollouts drift
                # exactly like unchunked ones.  exp/log1p instead of a
                # float pow for a clean Mosaic lowering.
                n = jnp.asarray(drift_n0 + 4 * (chunk_step0 + t),
                                jnp.float32)
                dfac = jnp.exp(jnp.float32(-drift_nu)
                               * jnp.log1p(n / jnp.float32(drift_tau)))
            else:
                dfac = None
            k1 = f(u_ref[0, 2 * t], y, step_salt, dfac)
            k2 = f(u_ref[0, 2 * t + 1], y + (dt / 2) * k1,
                   step_salt + 2 * num_layers, dfac)
            k3 = f(u_ref[0, 2 * t + 1], y + (dt / 2) * k2,
                   step_salt + 4 * num_layers, dfac)
            k4 = f(u_ref[0, 2 * t + 2], y + dt * k3,
                   step_salt + 6 * num_layers, dfac)
            y = y + (dt / 6) * (k1 + 2 * k2 + 2 * k3 + k4)
            out_ref[t] = y
            return y

        carry_ref[...] = lax.fori_loop(0, C, body, carry_ref[...])

    return kernel


def fused_analogue_rollout(
    gps: Sequence[jax.Array],     # per layer (K_l + 1, N_l): conductances
    gms: Sequence[jax.Array],     # (f32) or uint8 level indices; bias row last
    scales: jax.Array,            # (L,) per-tensor programming scales
    y0: jax.Array,                # (B, D) float32
    u_half: jax.Array,            # (2T+1, Du) shared or (B, 2T+1, Du)
    dt: float,
    *,
    g_step: float | None = None,  # set => uint8 quantised storage
    g_min: float = 0.0,           # conductance floor (noisy quantised reads)
    g_max: float = 0.0,           # conductance ceiling (stuck overrides)
    v_clamp: float | None = None,
    read_noise: float = 0.0,
    noise_seed: int = 0,
    step_offset: int = 0,         # global step index of y0 (resume replay)
    fault: dict | None = None,    # FaultModel.kernel_args(); None = healthy
    batch_tile: int = 64,
    time_chunk: int | None = None,
    interpret: bool | None = None,
    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
) -> jax.Array:
    """Full-trajectory analogue RK4 solve; returns (T+1, B, D) float32.

    Same contract as ``fused_node_rollout`` (uniform grid, half-step
    drive, batch tiling, VMEM-budgeted time chunking) with the crossbar
    read semantics of ``core.analogue.analogue_mlp_apply`` traced
    in-kernel.  See the module docstring for the noise model.

    ``fault`` (a ``FaultModel.kernel_args()`` dict of static scalars)
    injects device faults in-kernel: stuck cells pinned at their global
    coordinates (bitwise the program-time masks of
    :mod:`repro.core.faults`) and live read-disturb drift whose decay
    exponent advances with the global step count.

    ``step_offset`` declares the global RK4 step index of ``y0``: a
    rollout resumed mid-trajectory (streaming serving, see
    ``docs/serving.md``) passes the number of steps already served so
    the per-step noise salts and the drift exponent continue the SAME
    global streams an uninterrupted rollout would have used — with it,
    split-and-resume noisy rollouts are bitwise-identical to unsplit
    ones.  It is a compile-time constant (one compiled program per
    distinct offset); noise-free, drift-free solves ignore it.
    """
    if interpret is None:
        interpret = default_interpret()
    if read_noise > 0.0 and g_step is not None and g_min <= 0.0:
        raise ValueError(
            "fused_analogue_rollout: noisy quantised reads need the "
            "absolute conductance floor — pass g_min > 0 (spec.g_min)")
    fa = dict(_FAULT_DEFAULTS, **(fault or {}))
    if set(fa) != set(_FAULT_DEFAULTS):
        raise ValueError(
            f"fused_analogue_rollout: unknown fault keys "
            f"{sorted(set(fa) - set(_FAULT_DEFAULTS))}; have "
            f"{sorted(_FAULT_DEFAULTS)}")
    if fa["stuck_rate"] > 0.0 and not g_max > g_min:
        raise ValueError(
            "fused_analogue_rollout: stuck-cell injection pins cells to "
            "the absolute G_on/G_off values — pass g_max > g_min "
            "(spec.g_max/spec.g_min)")
    y0 = y0.astype(jnp.float32)
    u_half = u_half.astype(jnp.float32)
    scales = jnp.asarray(scales, jnp.float32)
    gps = list(gps)
    gms = list(gms)
    L = len(gps)
    if scales.shape != (L,):
        raise ValueError(
            f"fused_analogue_rollout: scales must be ({L},), got "
            f"{scales.shape}")

    B, D = y0.shape
    per_tile_drive = u_half.ndim == 3
    if per_tile_drive and u_half.shape[0] != B:
        raise ValueError(
            f"per-twin drive batch {u_half.shape[0]} != y0 batch {B}")
    if per_tile_drive and u_half.shape[-1] == 0:
        per_tile_drive, u_half = False, u_half[0]
    T = (u_half.shape[1 if per_tile_drive else 0] - 1) // 2
    du = u_half.shape[-1]
    bt = min(batch_tile, B)
    if B % bt:
        raise ValueError(f"batch {B} not divisible by tile {bt}")

    # VMEM plan: the stationary operands are the TWO conductance arrays
    # per layer (the pair never combines in HBM), so size the plan on
    # both; activation slack is that of the effective (K, N) weights.
    plan = plan_time_chunk(T, bt, D, du, per_tile_drive,
                           [g.astype(jnp.float32) for g in gps + gms], [],
                           vmem_budget_bytes, time_chunk, precision="f32")
    C, NC = plan.time_chunk, plan.num_chunks

    kernel = _make_kernel(L, C, float(dt), du, bt, per_tile_drive,
                          None if g_step is None else float(g_step),
                          float(g_min), float(g_max), v_clamp,
                          float(read_noise), int(noise_seed),
                          float(fa["stuck_rate"]),
                          float(fa["stuck_on_frac"]),
                          int(fa["fault_seed"]), int(fa["salt_base"]),
                          float(fa["drift_nu"]), float(fa["drift_tau"]),
                          int(fa["drift_n0"]), int(step_offset))

    grid = (B // bt, NC)
    u_in, u_spec = chunked_drive_operand(u_half, T, C, NC, bt,
                                         per_tile_drive, reverse=False)
    in_specs = [
        pl.BlockSpec((bt, D), lambda i, j: (i, 0)),      # y0
        u_spec,                                          # u_chunks
    ]
    for g in gps + gms:
        in_specs.append(pl.BlockSpec(g.shape, lambda i, j: (0, 0)))
    in_specs.append(pl.BlockSpec(scales.shape, lambda i, j: (0,)))
    out_spec = pl.BlockSpec((C, bt, D), lambda i, j: (j, i, 0))

    steps = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((NC * C, B, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
        name="fused_analogue",
        interpret=interpret,
    )(y0, u_in, *gps, *gms, scales)
    return jnp.concatenate([y0[None], steps[:T]], axis=0)
