"""Reverse-time fused neural-ODE solve — training on the serving substrate.

The forward kernel (:mod:`repro.kernels.fused_ode_mlp`) keeps the MLP
weights VMEM-resident for the whole RK4 trajectory.  This module gives
that rollout a custom VJP whose backward pass runs the SAME
weights-stationary discipline in reverse: a second Pallas kernel walks
the time-chunk grid dimension backwards, replays each chunk forward from
its chunk-boundary state (recompute-in-VMEM checkpointing — the
checkpoints are the chunk boundaries the forward already materialised as
trajectory rows), and accumulates ``(dL/dy0, dL/dW, dL/db)`` while the
weights and their gradient accumulators stay pinned in VMEM.

This is the discretise-then-optimise analogue of
:mod:`repro.core.adjoint`: instead of integrating a continuous adjoint
ODE step by step (one HBM round-trip per f-eval), the cotangent is
pulled back through the exact RK4 update whole-chunk-fused, so the
gradient matches backprop-through-the-unrolled-solver to float32
rounding.

Grid: (batch tiles, time chunks), time minor, chunks visited in REVERSE
order via the index maps.  Block layout per (i, j) cell (chunk
``jj = NC-1-j``):

  y_bound  (1, bt, D)        chunk jj's boundary state (traj row jj*C)
  u_chunks (1, 2C+1, Du)     chunk jj's drive half-steps (as forward)
  g        (C, bt, D)        cotangent slab for chunk jj's output rows
  w_l/b_l  (full)            broadcast — weights stay resident
  dy0      (bt, D)           per-tile block; last write (chunk 0) wins
  dw_l/db_l (full)           one block for the WHOLE grid — the VMEM
                             gradient accumulator (zeroed at the first
                             cell, accumulated in place, flushed once)
  a        (bt, D)  scratch  adjoint carried across chunks of one tile
  ys       (C, bt, D) scratch  replayed per-step states of the chunk

VMEM per cell ~= weights (operands, dw refs, dw loop carry) + TWO C-slabs
(replayed states + cotangents) + activation slack for the step VJP,
counted tiled like the forward's (``vmem_tile_bytes``) —
roughly twice the forward's footprint, so ``plan_bwd_time_chunk`` packs
a (usually smaller) chunk against the same budget.  The boundary states
are FREE residuals: the forward's output trajectory already contains
every chunk-start state as row ``jj*C``, so the VJP stores nothing
beyond what serving already returns.

Gradients are taken w.r.t. ``y0``, ``weights`` and ``biases``; the drive
``u_half`` is treated as data (zero cotangent) — it is a sampled input
signal, not a parameter.

Mixed precision mirrors the forward's ``precision`` policy: the
boundary states, cotangent slabs and weight operands stream at the
storage dtype (bf16 under the bf16 policies; the drive stays f32, as
in the forward), the replay
and the adjoint run at the carry dtype, and the dW/db gradient
accumulators — both the in-loop carry and the constant-index-map VMEM
output blocks — ALWAYS stay float32, so reduced storage never costs
accumulation accuracy across T steps.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET, DRIVE_DTYPE,
                                         ChunkPlan, MLPField,
                                         _rk4_activation_bytes,
                                         chunked_drive_operand,
                                         drive_block_shape,
                                         fused_node_rollout,
                                         largest_fitting_chunk,
                                         make_rk4_step, precision_dtypes,
                                         resolve_precision, vmem_tile_bytes)
from repro.kernels.platform import default_interpret


def plan_bwd_time_chunk(T: int, bt: int, D: int, du: int,
                        per_tile_drive: bool,
                        weights: Sequence[jax.Array],
                        biases: Sequence[jax.Array],
                        vmem_budget_bytes: int,
                        time_chunk: int | None = None,
                        precision: str = "f32") -> ChunkPlan:
    """Backward-pass chunk planner: same contract and tiled accounting as
    ``plan_time_chunk``, for the heavier reverse working set.  Pipelined
    blocks (count twice): the boundary row, the drive, the (C, bt, D)
    cotangent slab and the weights at the storage dtype; the dy0 block
    and the dW/db accumulator blocks, ALWAYS f32.  Once each: the adjoint
    and the (C, bt, D) replayed-state scratch at the carry dtype, the
    f32 gradient carry of the reverse loop plus one step's increment,
    and twice the forward's activation slack (primal residuals and
    cotangents live together)."""
    store, _, acc, carry = precision_dtypes(resolve_precision(precision))
    f32 = jnp.float32
    params = list(weights) + list(biases)
    grads = sum(vmem_tile_bytes(p.shape, f32) for p in params)
    fixed = (2 * (vmem_tile_bytes((1, bt, D), store)
                  + sum(vmem_tile_bytes(p.shape, store) for p in params)
                  + vmem_tile_bytes((bt, D), f32) + grads)
             + vmem_tile_bytes((bt, D), carry) + 2 * grads
             + 2 * _rk4_activation_bytes(bt, D, weights, acc))

    def need(C):
        return (fixed + vmem_tile_bytes((C, bt, D), carry)
                + 2 * (vmem_tile_bytes(
                    drive_block_shape(C, bt, du, per_tile_drive),
                    DRIVE_DTYPE)
                       + vmem_tile_bytes((C, bt, D), store)))

    return largest_fitting_chunk(T, need, vmem_budget_bytes, time_chunk,
                                 "fused backward")


def _make_bwd_kernel(num_layers: int, C: int, dt: float,
                     drive_dim: int, bt: int, per_tile_drive: bool,
                     precision: str = "f32"):
    L = num_layers
    _, _, _, carry_dt = precision_dtypes(resolve_precision(precision))
    # THE step of the forward kernel — shared so the checkpoint replay
    # recomputes bit-identical states and the VJP transposes the exact
    # update the forward applied (same precision policy included)
    rk4 = make_rk4_step(MLPField(L), dt, drive_dim, bt, per_tile_drive,
                        precision)

    def kernel(*refs):
        yb_ref, u_ref, g_ref = refs[0], refs[1], refs[2]
        w_refs = refs[3:3 + L]
        b_refs = refs[3 + L:3 + 2 * L]
        dy0_ref = refs[3 + 2 * L]
        dw_refs = refs[4 + 2 * L:4 + 3 * L]
        db_refs = refs[4 + 3 * L:4 + 4 * L]
        a_ref = refs[4 + 4 * L]
        ys_ref = refs[5 + 4 * L]

        i = pl.program_id(0)
        j = pl.program_id(1)       # j walks 0..NC-1; the chunk REVERSAL
        #                            lives in the BlockSpec index maps

        # First (reverse-)chunk of a batch tile: zero the adjoint carry.
        @pl.when(j == 0)
        def _():
            a_ref[...] = jnp.zeros_like(a_ref)

        # Very first grid cell: zero the in-VMEM gradient accumulators.
        @pl.when((i == 0) & (j == 0))
        def _():
            for r in dw_refs:
                r[...] = jnp.zeros_like(r)
            for r in db_refs:
                r[...] = jnp.zeros_like(r)

        ws = [w_ref[...] for w_ref in w_refs]
        bs = [b_ref[...] for b_ref in b_refs]

        # -- replay: recompute the chunk's per-step states into VMEM ----
        def fwd_body(t, y):
            ys_ref[t] = y
            return rk4(y, u_ref[0, 2 * t], u_ref[0, 2 * t + 1],
                       u_ref[0, 2 * t + 2], ws, bs)

        lax.fori_loop(0, C, fwd_body, yb_ref[0].astype(carry_dt))

        # -- reverse sweep: pull the cotangent back through each step ---
        # Per-step weight cotangents come back at the storage dtype (the
        # VJP transposes the bf16 operands); the ACCUMULATORS stay f32 —
        # both the fori_loop carry here and the dw_refs output blocks —
        # so T steps of bf16-rounded increments sum without drift.
        zeros_w = [jnp.zeros(w.shape, jnp.float32) for w in ws]
        zeros_b = [jnp.zeros(b.shape, jnp.float32) for b in bs]

        def bwd_body(r, carry):
            a, dws, dbs = carry
            t = C - 1 - r
            y_t = ys_ref[t]
            u0 = u_ref[0, 2 * t]
            um = u_ref[0, 2 * t + 1]
            u1 = u_ref[0, 2 * t + 2]
            # cotangent injected at this output row (adjoint stays at the
            # carry dtype — f32 unless the policy is pure bf16)
            a = a + g_ref[t].astype(a.dtype)
            _, vjp = jax.vjp(
                lambda y_, ws_, bs_: rk4(y_, u0, um, u1, ws_, bs_),
                y_t, ws, bs)
            a, dws_t, dbs_t = vjp(a)
            dws = [acc + d.astype(jnp.float32)
                   for acc, d in zip(dws, dws_t)]
            dbs = [acc + d.astype(jnp.float32)
                   for acc, d in zip(dbs, dbs_t)]
            return a, dws, dbs

        a, dws, dbs = lax.fori_loop(0, C, bwd_body,
                                    (a_ref[...], zeros_w, zeros_b))
        a_ref[...] = a
        # chunk 0 (the last j) leaves dL/dy0
        dy0_ref[...] = a.astype(jnp.float32)
        for ref, v in zip(dw_refs, dws):
            ref[...] += v
        for ref, v in zip(db_refs, dbs):
            ref[...] += v

    return kernel


def fused_node_rollout_bwd(
    y_bounds: jax.Array,              # (NC, B, D) chunk-boundary states
    u_half: jax.Array,                # (2T+1, Du) shared or (B, 2T+1, Du)
    weights: Sequence[jax.Array],
    biases: Sequence[jax.Array],
    g_steps: jax.Array,               # (T, B, D) cotangents for rows 1..T
    dt: float,
    *,
    batch_tile: int,
    time_chunk: int,                  # the C that produced y_bounds
    interpret: bool | None = None,
    precision: str = "f32",
) -> tuple:
    """Run the reverse-time kernel; returns ``(dy0, dweights, dbiases)``
    — always f32 (the gradient accumulators never leave full precision).

    ``y_bounds[jj]`` must be the state at the START of chunk jj (forward
    trajectory row ``jj*C``); ``g_steps`` are the cotangents of the
    forward's per-step outputs (trajectory rows 1..T — the y0 row's
    cotangent is added by the caller).  ``y_bounds``,
    ``weights``/``biases`` and ``g_steps`` are expected at the policy's
    storage dtype (the caller casts); the drive is cast to
    ``DRIVE_DTYPE`` here.
    """
    if interpret is None:
        interpret = default_interpret()
    precision = resolve_precision(precision)
    carry_dt = precision_dtypes(precision)[3]
    NC, B, D = y_bounds.shape
    C = int(time_chunk)
    per_tile_drive = u_half.ndim == 3
    if per_tile_drive and u_half.shape[-1] == 0:
        per_tile_drive, u_half = False, u_half[0]
    T = g_steps.shape[0]
    du = u_half.shape[-1]
    L = len(weights)
    bt = min(batch_tile, B)
    if B % bt:
        raise ValueError(f"batch {B} not divisible by tile {bt}")

    # zero-pad the cotangents over the padded tail of a partial final
    # chunk: the replayed padded steps then contribute exactly nothing.
    pad = NC * C - T
    if pad:
        g_steps = jnp.pad(g_steps, ((0, pad), (0, 0), (0, 0)))

    kernel = _make_bwd_kernel(L, C, float(dt), du, bt, per_tile_drive,
                              precision)

    grid = (B // bt, NC)
    u_in, u_spec = chunked_drive_operand(u_half, T, C, NC, bt,
                                         per_tile_drive, reverse=True)
    in_specs = [
        pl.BlockSpec((1, bt, D), lambda i, j: (NC - 1 - j, i, 0)),  # bounds
        u_spec,
        pl.BlockSpec((C, bt, D), lambda i, j: (NC - 1 - j, i, 0)),  # g
    ]
    for w in weights:
        in_specs.append(pl.BlockSpec(w.shape, lambda i, j: (0, 0)))
    for b in biases:
        in_specs.append(pl.BlockSpec(b.shape, lambda i, j: (0,)))

    out_shapes = ([jax.ShapeDtypeStruct((B, D), jnp.float32)]
                  + [jax.ShapeDtypeStruct(w.shape, jnp.float32)
                     for w in weights]
                  + [jax.ShapeDtypeStruct(b.shape, jnp.float32)
                     for b in biases])
    out_specs = ([pl.BlockSpec((bt, D), lambda i, j: (i, 0))]
                 + [pl.BlockSpec(w.shape, lambda i, j: (0, 0))
                    for w in weights]
                 + [pl.BlockSpec(b.shape, lambda i, j: (0,))
                    for b in biases])

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((bt, D), carry_dt),     # adjoint
                        pltpu.VMEM((C, bt, D), carry_dt)], # replayed ys
        name="fused_bwd",
        interpret=interpret,
    )(y_bounds, u_in, g_steps, *weights, *biases)
    dy0, dws, dbs = outs[0], list(outs[1:1 + L]), list(outs[1 + L:])
    return dy0, dws, dbs


# ---------------------------------------------------------------------------
# The differentiable rollout: custom VJP over (y0, u_half, weights, biases)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def fused_node_rollout_vjp(y0, u_half, weights, biases, dt,
                           batch_tile=64, time_chunk=None, interpret=None,
                           vmem_budget_bytes=DEFAULT_VMEM_BUDGET,
                           precision=None, field=None):
    """:func:`fused_node_rollout` with gradients that never leave the
    fused substrate: forward AND backward are whole-chunk Pallas kernels,
    weights pinned in VMEM both ways.  Differentiable in ``y0``,
    ``weights`` and ``biases``; the drive gets a zero cotangent.

    ``field`` (default: the MLP of ``weights``) is the in-kernel vector
    field; one without a fused backward (``field.differentiable`` false,
    the neural CDE) runs its forward here and raises
    ``NotImplementedError`` when differentiated.

    ``precision`` is a nondiff static: the forward casts the operands to
    the policy's storage dtype internally, and the backward returns
    cotangents at the PRIMAL dtypes — so f32 params in, f32 grads out,
    with the f32 in-kernel accumulators never rounded on the way back.

    The primal body uses the same shared (backward-planned) time chunk
    as the VJP pair, so a plain call and the forward inside
    ``jax.grad`` are bitwise identical even under the bf16 policies
    (where chunk boundaries are rounding points).
    """
    if field is None or field.differentiable:
        time_chunk = _shared_chunk(y0, u_half, weights, biases, batch_tile,
                                   time_chunk, vmem_budget_bytes, precision)
    return fused_node_rollout(y0, u_half, weights, biases, dt,
                              batch_tile=batch_tile, time_chunk=time_chunk,
                              interpret=interpret,
                              vmem_budget_bytes=vmem_budget_bytes,
                              precision=precision, field=field)


def _shared_chunk(y0, u_half, weights, biases, batch_tile, time_chunk,
                  vmem_budget_bytes, precision):
    """The time chunk BOTH passes of the VJP use: the backward planner's
    (heavier) auto-pick, or the explicit override.

    Sharing one C matters under the bf16 policies: the forward rounds
    its VMEM carry through the storage dtype exactly at chunk
    boundaries, so the chunk-start rows the backward replays from are
    bit-identical to the states the forward continued with ONLY when
    the two passes agree on where the boundaries are.  (Under f32 the
    carry is never rounded and the chunking is numerically free.)"""
    if time_chunk is not None:
        return time_chunk
    B, D = y0.shape
    T = (u_half.shape[1 if u_half.ndim == 3 else 0] - 1) // 2
    du = u_half.shape[-1]
    per_tile = u_half.ndim == 3 and du > 0
    plan = plan_bwd_time_chunk(T, min(batch_tile, B), D, du, per_tile,
                               weights, biases, vmem_budget_bytes, None,
                               precision=resolve_precision(precision))
    return plan.time_chunk


def _rollout_fwd(y0, u_half, weights, biases, dt, batch_tile, time_chunk,
                 interpret, vmem_budget_bytes, precision, field):
    if field is not None and not field.differentiable:
        raise NotImplementedError(
            f"the fused substrate has no backward for {type(field).__name__}"
            f": differentiate this field on the digital backend, or pass "
            f"gradient='stopgrad' to serve it")
    traj = fused_node_rollout(y0, u_half, weights, biases, dt,
                              batch_tile=batch_tile,
                              time_chunk=_shared_chunk(
                                  y0, u_half, weights, biases, batch_tile,
                                  time_chunk, vmem_budget_bytes, precision),
                              interpret=interpret,
                              vmem_budget_bytes=vmem_budget_bytes,
                              precision=precision)
    # The trajectory IS the residual: every chunk-boundary state the
    # backward replays from is already a row of the primal output (at
    # the storage dtype — the forward rounds its chunk-boundary carry to
    # match, so the replay is still bit-identical), and checkpointing
    # costs zero extra memory traffic.  The empty y0-dtype marker lets
    # the backward return dL/dy0 at the primal dtype.
    return traj, (u_half, weights, biases, traj,
                  jnp.zeros((0,), y0.dtype))


def _rollout_bwd(dt, batch_tile, time_chunk, interpret, vmem_budget_bytes,
                 precision, field, res, g):
    del field               # only the MLP gets here (``_rollout_fwd``)
    u_half, weights, biases, traj, y0_marker = res
    precision = resolve_precision(precision)
    store, _, _, _ = precision_dtypes(precision)
    u_orig, w_orig, b_orig = u_half, weights, biases
    # the kernel consumes the storage-dtype operands the forward ran on
    weights = [w.astype(store) for w in weights]
    biases = [b.astype(store) for b in biases]
    u_half = u_half.astype(DRIVE_DTYPE)
    B, D = traj.shape[1], traj.shape[2]
    per_tile_drive = u_half.ndim == 3
    if per_tile_drive and u_half.shape[-1] == 0:
        per_tile_drive, u_half = False, u_half[0]
    T = (u_half.shape[1 if per_tile_drive else 0] - 1) // 2
    du = u_half.shape[-1]
    bt = min(batch_tile, B)
    plan = plan_bwd_time_chunk(T, bt, D, du, per_tile_drive, weights,
                               biases, vmem_budget_bytes, time_chunk,
                               precision=precision)
    C, NC = plan.time_chunk, plan.num_chunks
    y_bounds = traj[jnp.arange(NC) * C]              # chunk-start states
    # the y0 row's cotangent never enters the kernel — keep it f32; only
    # the per-step slab streams at storage width
    g0 = g[0].astype(jnp.float32)
    dy0, dws, dbs = fused_node_rollout_bwd(
        y_bounds, u_half, weights, biases, g[1:].astype(store), dt,
        batch_tile=batch_tile, time_chunk=C, interpret=interpret,
        precision=precision)
    dy0 = (dy0 + g0).astype(y0_marker.dtype)
    # cotangents must match the PRIMAL avals (f32 params stay f32)
    dws = [d.astype(w.dtype) for d, w in zip(dws, w_orig)]
    dbs = [d.astype(b.dtype) for d, b in zip(dbs, b_orig)]
    # drive is data, not a parameter — zero cotangent (see module doc)
    return dy0, jnp.zeros_like(u_orig), dws, dbs


fused_node_rollout_vjp.defvjp(_rollout_fwd, _rollout_bwd)
