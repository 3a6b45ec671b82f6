"""Weights-stationary fused neural-ODE solve — the paper's in-memory insight
on TPU.

The analogue system's whole advantage is that weights never move: they sit
in the crossbar while the state circulates through the closed loop.  The
TPU transposition: pin the MLP weights in VMEM once and run the ENTIRE RK4
trajectory (T steps x 4 f-evals) inside a single ``pallas_call`` —
activations live in VREGs/VMEM, the only HBM traffic is y0/drive in and
the trajectory out.  A step-by-step XLA implementation would re-read the
weights from HBM every f-eval and write every intermediate state back; at
the paper's sizes that makes the solve HBM-latency-bound.

The vector field is a parameter of the in-kernel step: :class:`MLPField`
(the ReLU MLP dy/dt = MLP([u, y])) or :class:`CDEField` (the neural CDE
dz/dt = tanh(MLP(z)) · dX/dt, its control derivative the drive).

Grid: (batch tiles, time chunks); weights broadcast to every cell.  Time
is the minor grid dimension, so all chunks of one batch tile run back to
back and the integration state is carried across chunks in a VMEM scratch
buffer (re-seeded from ``y0`` whenever a new batch tile starts).
Block layout per (i, j) cell:
  y0       (bt, D)            per-tile, same block for every chunk
  u_chunks (1, 2C+1, Du)      chunk j's drive half-steps, broadcast
           — or, for per-twin drives (fleet serving), (1, 2C+1, bt, Du)
           per-tile slices of a (n_chunks, 2C+1, B, Du) stimulus tensor
  w_i/b_i  (full)             broadcast — the "crossbar residency"
  out      (C, bt, D)         chunk j's slab of the trajectory
  carry    (bt, D)            VMEM scratch, persistent across the grid

VMEM per cell is counted as the chip lays it out (:func:`vmem_tile_bytes`:
the last two dims of every buffer padded to the (sublane, 128-lane)
tile, two buffers per pipelined block): weights + the (C, bt, D) out
slab + the (2C+1)-row drive slab + carry + activations.  The horizon T
does not have to fit — only one chunk does.  ``time_chunk=None``
auto-picks the largest C within ``vmem_budget_bytes``, so weights stay
resident while arbitrarily long horizons stream chunk-by-chunk through
HBM.  A ``ValueError`` is raised only when the weights plus a single
step cannot fit.

Mixed precision: the ``precision`` policy decides the byte width of
the weights and trajectory slabs.  ``"bf16_f32acc"`` (the TPU default)
stores weights and trajectory slabs in bfloat16 — halving HBM traffic
and roughly doubling the resident time chunk — while every ``jnp.dot``
accumulates at float32 on the MXU and the RK4 state carry stays float32
in VMEM scratch.  The drive slab stays float32 under every policy: the
kernel reads it one half-step row at a time, which Mosaic cannot do on
a bf16 slab (two rows share a sublane), and the step casts the drive to
the compute dtype before any matmul, so the result is the same as with
a bf16 slab.  ``"bf16"`` additionally carries the state at bfloat16
(the fully reduced substrate, mirroring
the analogue crossbar's precision tolerance); ``"f32"`` is the exact
float32 path.  In the bf16 policies the carried state is rounded to
the storage dtype once per chunk boundary, so the chunk-start states
the backward pass replays from (the stored trajectory rows) are
bit-identical to the states the forward actually continued from.

This module is the forward; :mod:`repro.kernels.fused_ode_mlp_bwd`
walks the same grid in reverse (chunk-boundary checkpoints = trajectory
rows, recompute-in-VMEM replay) to make the rollout differentiable on
the same substrate.

Resuming mid-trajectory (the streaming-serving contract, enforced by
``tests/test_streaming.py``): because the carried state is rounded
through the storage dtype at every chunk boundary AND the y0 seed takes
the same ``.astype(store).astype(carry)`` path, any stored trajectory
row ``traj[k]`` is the exact value the kernel continued integrating
from — so ``fused_node_rollout(traj[k], drive_window(u_half, k, T-k),
...)`` reproduces rows ``k..T`` of the uninterrupted solve
bit-identically under "f32" (the seed round-trip is a no-op) and under
pure "bf16" (rows are stored at the carry dtype).  Under
"bf16_f32acc" the intra-chunk carry is f32 but rows are stored bf16,
so resuming at a non-chunk-boundary step re-rounds the seed once:
parity within one storage-dtype rounding of the carried state.  The
drive must be re-sampled on the canonical global half-step grid
(:func:`repro.kernels.ops.half_step_times`) — re-deriving it with
``linspace`` over the sub-window perturbs t by ~1 ulp and breaks
bitwise parity.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import default_interpret


#: Per-cell VMEM the chunk planners fill.  Mosaic's default scoped-VMEM
#: limit for one kernel on a TPU v5e is 16 MiB.  The planners count
#: buffers as the chip lays them out (tiled, two per pipelined block —
#: :func:`vmem_tile_bytes`) and leave the other 4 MiB to the compiler's
#: own scratch (spilled loop values, matmul temporaries), so a planned
#: kernel compiles without raising the limit.
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024

#: The drive slab's dtype in HBM and VMEM (see the module docstring).
DRIVE_DTYPE = jnp.float32

#: Supported precision policies (see the module docstring's error model).
PRECISIONS = ("f32", "bf16", "bf16_f32acc")


def default_precision() -> str:
    """``"bf16_f32acc"`` on TPU (MXU-native bf16, f32 accumulation),
    ``"f32"`` everywhere else — CPU/GPU hosts validate exact numerics."""
    return "bf16_f32acc" if jax.default_backend() == "tpu" else "f32"


def resolve_precision(precision: str | None) -> str:
    """Accept a policy name or None (auto: :func:`default_precision`)."""
    if precision is None:
        return default_precision()
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; have {list(PRECISIONS)}")
    return precision


def precision_dtypes(precision: str):
    """``(store, compute, acc, carry)`` dtypes of a resolved policy.

    store   — weights/biases and trajectory slabs (HBM + the
              VMEM-resident operand blocks; the drive stays at
              :data:`DRIVE_DTYPE`);
    compute — matmul operand dtype fed to the MXU;
    acc     — dtype of every in-kernel ``jnp.dot`` result (the MXU sums
              at f32; pure ``"bf16"`` rounds the sum to bf16);
    carry   — the RK4 integration state in VMEM scratch.
    """
    if precision == "f32":
        return (jnp.float32,) * 4
    if precision == "bf16":
        return (jnp.bfloat16,) * 4
    if precision == "bf16_f32acc":
        return jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.float32
    raise ValueError(
        f"unknown precision {precision!r}; have {list(PRECISIONS)}")


def _require_float(name: str, x: jax.Array, precision: str) -> None:
    """Clear dtype gate: a non-floating input would otherwise reach the
    kernel and die with an opaque Mosaic lowering error."""
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        raise ValueError(
            f"fused_node_rollout: {name} has non-floating dtype "
            f"{jnp.asarray(x).dtype}; the precision={precision!r} policy "
            f"stores {jnp.dtype(precision_dtypes(precision)[0]).name} — "
            f"cast {name} to a floating dtype first")


class ChunkPlan(NamedTuple):
    """How a T-step horizon is streamed through VMEM."""
    time_chunk: int          # C — RK4 steps resident per grid cell
    num_chunks: int          # ceil(T / C)
    vmem_bytes: int          # estimated per-cell VMEM footprint


def vmem_tile_bytes(shape: Sequence[int], dtype) -> int:
    """Bytes one VMEM buffer of ``shape`` occupies on the chip.

    Mosaic tiles the last two dims: the minor dim is padded to 128 lanes
    and the second-minor to whole sublane groups — 8 rows for 32-bit
    types, 16 for 16-bit (two rows packed per sublane), 32 for 8-bit.
    A rank-1 buffer is one such row.  So a (C, bt, 1) slab takes 128x
    its logical bytes, which a plain ``size * itemsize`` count misses.
    """
    itemsize = jnp.dtype(dtype).itemsize
    shape = tuple(shape)
    if len(shape) < 2:
        shape = (1,) * (2 - len(shape)) + shape
    *lead, rows, cols = shape
    sublanes = 8 * (4 // itemsize)
    rows = -(-rows // sublanes) * sublanes
    cols = -(-cols // 128) * 128
    return math.prod(lead) * rows * cols * itemsize


def drive_block_shape(C: int, bt: int, du: int,
                      per_tile_drive: bool) -> tuple:
    """The drive slab's per-cell block: ``(1, 2C+1, bt, Du)`` per-twin,
    ``(1, 2C+1, max(Du, 1))`` shared (autonomous fields get one zero
    column)."""
    if per_tile_drive:
        return (1, 2 * C + 1, bt, du)
    return (1, 2 * C + 1, max(du, 1))


def _rk4_activation_bytes(bt: int, D: int, weights: Sequence[jax.Array],
                          acc_dtype) -> int:
    """VMEM slack for the live RK4 temporaries of one step.

    Derived from what one ``make_rk4_step`` invocation actually keeps
    alive at its peak, all at the accumulation dtype:

      6 · (bt, D)          y, k1..k4 and the perturbed state y + c·k_i
                           (the final combination holds all four k's plus
                           y at once — six state-width buffers)
      (bt, in_l), (bt, out_l)
                           the widest adjacent (input, output) activation
                           pair of the MLP — at any moment one layer's
                           input and its dot output coexist; the first
                           layer's input width already includes du + D
                           through w_0.shape[0]

    each counted tiled (:func:`vmem_tile_bytes`).
    """
    state = vmem_tile_bytes((bt, D), acc_dtype)
    widest_pair = max(vmem_tile_bytes((bt, w.shape[0]), acc_dtype)
                      + vmem_tile_bytes((bt, w.shape[1]), acc_dtype)
                      for w in weights)
    return 6 * state + widest_pair


def largest_fitting_chunk(T: int, need: Callable[[int], int],
                          vmem_budget_bytes: int,
                          time_chunk: int | None, what: str) -> ChunkPlan:
    """Shared search of both chunk planners: the largest C in [1, T]
    with ``need(C) <= vmem_budget_bytes`` (``need`` grows with C), or
    the explicit ``time_chunk`` after checking that it fits."""
    mib = lambda n: f"{n / 2 ** 20:.1f} MiB"
    if time_chunk is not None:
        C = max(1, min(int(time_chunk), T))
        if need(C) > vmem_budget_bytes:
            # fail with a clear message instead of an opaque Mosaic
            # allocation error at lowering time
            raise ValueError(
                f"{what}: time_chunk={C} needs ~{mib(need(C))} VMEM "
                f"(budget {mib(vmem_budget_bytes)}); shrink time_chunk "
                f"or batch_tile")
        return ChunkPlan(C, -(-T // C), need(C))
    if need(1) > vmem_budget_bytes:
        raise ValueError(
            f"{what}: weights + one RK4 step need ~{mib(need(1))} VMEM "
            f"(budget {mib(vmem_budget_bytes)}); shrink batch_tile or "
            f"the MLP")
    lo, hi = 1, max(1, T)
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if need(mid) <= vmem_budget_bytes:
            lo = mid
        else:
            hi = mid - 1
    return ChunkPlan(lo, -(-T // lo), need(lo))


def plan_time_chunk(T: int, bt: int, D: int, du: int, per_tile_drive: bool,
                    weights: Sequence[jax.Array], biases: Sequence[jax.Array],
                    vmem_budget_bytes: int,
                    time_chunk: int | None = None,
                    precision: str = "f32", field=None) -> ChunkPlan:
    """Pick the largest time chunk C whose per-cell working set fits the
    VMEM budget (or honour an explicit ``time_chunk`` override).
    ``weights`` are the kernel's staged operands (``field.stage``);
    ``field`` (default: the MLP of those weights) sets the activation
    slack.

    Per-cell bytes, every buffer counted tiled (:func:`vmem_tile_bytes`)
    and at its policy dtype; pipelined blocks count twice (Pallas
    double-buffers them):

      2 · (y0 block + weights + biases)   resident operands
      2 · (C, bt, D) out slab             at the storage dtype
      2 · drive block                     f32 (:func:`drive_block_shape`)
      (bt, D) carry scratch               at the carry dtype
      RK4 activation slack                ``field.activation_bytes``

    bf16 storage halves the out slab, so the planned chunk is ~2x the
    f32 one at a fixed budget (the weights-must-fit threshold moves by
    about the same factor).
    """
    store, _, acc, carry = precision_dtypes(resolve_precision(precision))
    if field is None:
        field = MLPField(len(weights))
    fixed = (2 * (vmem_tile_bytes((bt, D), jnp.float32)
                  + sum(vmem_tile_bytes(w.shape, store) for w in weights)
                  + sum(vmem_tile_bytes(b.shape, store) for b in biases))
             + vmem_tile_bytes((bt, D), carry)
             + field.activation_bytes(bt, D, weights, acc))

    def need(C):
        return fixed + 2 * (
            vmem_tile_bytes(drive_block_shape(C, bt, du, per_tile_drive),
                            DRIVE_DTYPE)
            + vmem_tile_bytes((C, bt, D), store))

    return largest_fitting_chunk(T, need, vmem_budget_bytes, time_chunk,
                                 "fused kernel")


@dataclasses.dataclass(frozen=True)
class MLPField:
    """The in-kernel ReLU MLP field dy/dt = MLP([u, y]), or MLP(y) where
    the drive has no channels: ReLU between layers, none on the output.
    The fused backward (:mod:`repro.kernels.fused_ode_mlp_bwd`) replays
    and transposes it."""
    num_layers: int

    differentiable = True

    def stage(self, weights, biases):
        """The kernel's operands: the layers as they are."""
        return list(weights), list(biases)

    def activation_bytes(self, bt: int, D: int, weights, acc_dtype) -> int:
        return _rk4_activation_bytes(bt, D, weights, acc_dtype)

    def __call__(self, u, y, ws, bs, linear):
        x = y if u is None else jnp.concatenate([u, y], axis=-1)
        for i in range(self.num_layers):
            x = linear(x, ws[i], bs[i])
            if i < self.num_layers - 1:
                x = jnp.maximum(x, 0.0)
        return x


@dataclasses.dataclass(frozen=True)
class CDEField:
    """The in-kernel neural CDE field dz/dt = f(z) · dX/dt (Kidger et
    al. 2020): f(z) = tanh(MLP(z)), ReLU between the MLP's layers, its
    (D·Du)-wide output read as a (D, Du) matrix and contracted with the
    twin's Du-channel control derivative, which is the kernel's drive.

    The MLP's matmuls follow the precision policy; tanh and the
    contraction run at f32.  The output layer is staged channel-major,
    one 128-lane block per channel (:meth:`stage`), so the contraction
    is Du lane-aligned slices of the tanh tile, each scaled by one
    control column and summed on the VPU: no reshape of the tile crosses
    lanes.  No fused backward exists for it."""
    num_layers: int
    state_dim: int
    channels: int

    differentiable = False

    @property
    def block(self) -> int:
        """Lanes of one channel's block of the staged output layer."""
        return -(-self.state_dim // 128) * 128

    def stage(self, weights, biases):
        """The kernel's operands: the hidden layers as they are; the output
        layer's column i·Du + c (entry (i, c) of the source's (D, Du)
        view) moved to c·block + i, the padding columns zero (tanh(0) = 0
        adds nothing)."""
        D, Du, P = self.state_dim, self.channels, self.block
        w, b = weights[-1], biases[-1]
        if w.shape[1] != D * Du:
            raise ValueError(f"CDEField: the output layer has {w.shape[1]} "
                             f"columns, the field needs D·Du = {D * Du}")
        w = jnp.transpose(w.reshape(w.shape[0], D, Du), (0, 2, 1))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, P - D))).reshape(-1, Du * P)
        b = jnp.pad(b.reshape(D, Du).T, ((0, 0), (0, P - D))).reshape(Du * P)
        return list(weights[:-1]) + [w], list(biases[:-1]) + [b]

    def activation_bytes(self, bt: int, D: int, weights, acc_dtype) -> int:
        """The MLP's (as :func:`_rk4_activation_bytes`) and three f32
        (bt, Du·block) tiles: the output layer's sum, its tanh and one
        scaled block sum in flight."""
        f32 = jnp.float32
        return (_rk4_activation_bytes(bt, D, weights, acc_dtype)
                + 3 * vmem_tile_bytes((bt, weights[-1].shape[1]), f32))

    def __call__(self, u, z, ws, bs, linear):
        x = z
        for i in range(self.num_layers):
            x = linear(x, ws[i], bs[i])
            if i < self.num_layers - 1:
                x = jnp.maximum(x, 0.0)
        g = jnp.tanh(x.astype(jnp.float32))
        u = u.astype(jnp.float32)
        P = self.block
        dz = g[:, :P] * u[:, 0:1]
        for c in range(1, self.channels):
            dz = dz + g[:, c * P:(c + 1) * P] * u[:, c:c + 1]
        return dz[:, :self.state_dim]


def make_rk4_step(field, dt: float, drive_dim: int, bt: int,
                  per_tile_drive: bool, precision: str = "f32"):
    """One in-kernel RK4 step ``step(y, u0, um, u1, ws, bs) -> y_next``
    of the in-kernel ``field`` (:class:`MLPField`, :class:`CDEField`).

    SHARED between the forward kernel and the backward kernel's
    checkpoint replay + step VJP (:mod:`repro.kernels.fused_ode_mlp_bwd`)
    — the recompute must be bit-identical to the forward, so there is
    exactly one definition of the step.

    Under a bf16 ``precision`` policy the matmul operands are cast to
    the compute dtype (MXU-native bf16) and every ``jnp.dot`` sums at
    f32 and rounds to the policy's accumulation dtype; the
    surrounding RK4 arithmetic runs at the carry dtype (f32 for
    ``"bf16_f32acc"``), so only the MXU operands are reduced."""
    _, compute, acc, carry = precision_dtypes(resolve_precision(precision))
    # Mosaic's default f32 matmul is one bf16 MXU pass; the f32 policy
    # asks for full f32 products (interpret mode computes them anyway)
    dot_precision = lax.Precision.HIGHEST if compute == jnp.float32 else None

    def linear(x, w, b):
        # the MXU accumulates at f32 (Mosaic refuses a bf16
        # accumulator); pure "bf16" rounds the sum once afterwards
        x = jnp.dot(x.astype(compute), w, precision=dot_precision,
                    preferred_element_type=jnp.float32).astype(acc)
        # widen BEFORE broadcasting: the VJP of a width-1 bias sums
        # to a scalar, and Mosaic extracts only 32-bit scalars
        return x + b.astype(acc)[None, :]

    def f(u_row, y, ws, bs):
        u = None
        if drive_dim > 0:
            # u_row: (drive_dim,) broadcast, or (bt, drive_dim) per-twin
            u = (u_row if per_tile_drive
                 else jnp.broadcast_to(u_row, (bt, drive_dim))).astype(carry)
        return field(u, y, ws, bs, linear).astype(carry)

    def step(y, u0, um, u1, ws, bs):
        k1 = f(u0, y, ws, bs)
        k2 = f(um, y + (dt / 2) * k1, ws, bs)
        k3 = f(um, y + (dt / 2) * k2, ws, bs)
        k4 = f(u1, y + dt * k3, ws, bs)
        return y + (dt / 6) * (k1 + 2 * k2 + 2 * k3 + k4)

    return step


def pad_fleet_to_tile(y0s: jax.Array, uh: jax.Array, batch_tile: int):
    """Pad the fleet axis up to a multiple of the batch tile.

    Padded rows replicate the last twin (in-distribution values, no NaN
    risk) and per-twin drive slabs (``uh.ndim == 3``) are replicated
    alongside; the caller slices the result back to the real fleet.
    Returns ``(y0s_padded, uh_padded, bt, B)`` with ``B`` the original
    fleet size.  One extra tile instead of the old largest-divisor
    search that degenerated to bt=1 for prime fleet sizes.
    """
    B = y0s.shape[0]
    bt = min(batch_tile, B)
    pad = (-B) % bt
    if pad:
        y0s = jnp.concatenate(
            [y0s, jnp.broadcast_to(y0s[-1:], (pad,) + y0s.shape[1:])])
        if uh.ndim == 3:
            uh = jnp.concatenate(
                [uh, jnp.broadcast_to(uh[-1:], (pad,) + uh.shape[1:])])
    return y0s, uh, bt, B


def drive_window(u_half: jax.Array, start_step: int,
                 num_steps: int) -> jax.Array:
    """Slice a pre-sampled half-step drive to a resume window.

    ``u_half`` is the full-horizon drive on the RK4 half-step grid —
    (2T+1, Du) shared or (B, 2T+1, Du) per-twin; the window covering
    global steps ``[start_step, start_step + num_steps)`` is rows
    ``[2*start_step, 2*(start_step + num_steps)]`` inclusive (adjacent
    windows share their boundary sample, exactly like the kernel's own
    chunked drive slabs).  Handing this window to
    ``fused_node_rollout`` together with trajectory row ``start_step``
    as ``y0`` continues the solve bit-identically (see module doc).
    """
    axis = 1 if u_half.ndim == 3 else 0
    lo, hi = 2 * start_step, 2 * (start_step + num_steps) + 1
    if not (0 <= lo < hi <= u_half.shape[axis]):
        raise ValueError(
            f"drive_window: steps [{start_step}, {start_step + num_steps})"
            f" fall outside the (2T+1)={u_half.shape[axis]} half-step grid")
    return u_half[:, lo:hi] if axis == 1 else u_half[lo:hi]


def _make_kernel(field, C: int, dt: float, drive_dim: int,
                 bt: int, per_tile_drive: bool = False,
                 precision: str = "f32"):
    store, _, _, carry = precision_dtypes(resolve_precision(precision))
    num_layers = field.num_layers
    step = make_rk4_step(field, dt, drive_dim, bt, per_tile_drive,
                         precision)

    def kernel(*refs):
        y0_ref = refs[0]
        u_ref = refs[1]
        w_refs = refs[2:2 + num_layers]
        b_refs = refs[2 + num_layers:2 + 2 * num_layers]
        out_ref = refs[2 + 2 * num_layers]
        carry_ref = refs[3 + 2 * num_layers]

        # First chunk of a batch tile: seed the carried state from y0,
        # rounded through the storage dtype so the seed equals trajectory
        # row 0 exactly (what the backward pass replays chunk 0 from).
        @pl.when(pl.program_id(1) == 0)
        def _():
            carry_ref[...] = y0_ref[...].astype(store).astype(carry)

        # Load weights ONCE per cell — they stay register/VMEM-resident
        # for the whole chunk (the crossbar analogy).
        ws = [w_ref[...] for w_ref in w_refs]
        bs = [b_ref[...] for b_ref in b_refs]

        def body(t, y):
            y = step(y, u_ref[0, 2 * t], u_ref[0, 2 * t + 1],
                     u_ref[0, 2 * t + 2], ws, bs)
            out_ref[t] = y.astype(store)
            return y

        y = lax.fori_loop(0, C, body, carry_ref[...])
        # Round the chunk-boundary carry through the storage dtype: the
        # next chunk then continues from the exact value the trajectory
        # row stores, keeping forward and checkpoint-replay bit-identical
        # under reduced-precision storage (no-op for f32).
        carry_ref[...] = y.astype(store).astype(carry)

    return kernel


def _chunk_drive(u: jax.Array, C: int, num_chunks: int) -> jax.Array:
    """Re-slab a time-major drive (2T+1, ...) into per-chunk overlapping
    windows (num_chunks, 2C+1, ...).  Consecutive RK4 chunks share their
    boundary half-step sample, and the tail is edge-padded so a partial
    final chunk integrates on a frozen drive (those steps are sliced off
    the trajectory before returning)."""
    pad = 2 * (num_chunks * C) + 1 - u.shape[0]
    if pad:
        u = jnp.pad(u, ((0, pad),) + ((0, 0),) * (u.ndim - 1), mode="edge")
    idx = (jnp.arange(num_chunks) * 2 * C)[:, None] + jnp.arange(2 * C + 1)
    return u[idx]


def chunked_drive_operand(u_half: jax.Array, T: int, C: int, NC: int,
                          bt: int, per_tile_drive: bool, *, reverse: bool):
    """The drive operand of the fused kernels and its BlockSpec.

    ``u_half`` is (2T+1, Du) shared or (B, 2T+1, Du) per-twin; it is
    re-slabbed time-major into per-chunk windows (so the kernel's
    ``u_ref[0, 2t]`` indexing holds) at :data:`DRIVE_DTYPE`.  Grid cell
    (i, j) reads chunk j — or chunk NC-1-j with ``reverse`` (the
    backward kernel's walk)."""
    chunk = (lambda j: NC - 1 - j) if reverse else (lambda j: j)
    du = u_half.shape[-1]
    if per_tile_drive:
        u_tm = jnp.transpose(u_half, (1, 0, 2))          # (2T+1, B, du)
        index = lambda i, j: (chunk(j), 0, i, 0)
    else:
        u_tm = u_half if du > 0 else jnp.zeros((2 * T + 1, 1))
        index = lambda i, j: (chunk(j), 0, 0)
    u_in = _chunk_drive(u_tm.astype(DRIVE_DTYPE), C, NC)
    return u_in, pl.BlockSpec(drive_block_shape(C, bt, du, per_tile_drive),
                              index)


def rollout_plan(B: int, D: int, u_shape: Sequence[int],
                 weights: Sequence[jax.Array], biases: Sequence[jax.Array],
                 *, batch_tile: int = 64, time_chunk: int | None = None,
                 vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
                 precision: str | None = None, field=None) -> ChunkPlan:
    """The plan :func:`fused_node_rollout` takes for a (B, D) batch, a
    half-step drive of shape ``u_shape`` and the field's layers in
    source order, from their shapes alone: a caller counts the kernel's
    time chunks without tracing a solve."""
    if field is None:
        field = MLPField(len(weights))
    weights, biases = jax.eval_shape(field.stage, list(weights),
                                     list(biases))
    du = u_shape[-1]
    return plan_time_chunk((u_shape[-2] - 1) // 2, min(batch_tile, B), D,
                           du, len(u_shape) == 3 and du > 0, weights, biases,
                           vmem_budget_bytes, time_chunk,
                           precision=resolve_precision(precision),
                           field=field)


def fused_node_rollout(
    y0: jax.Array,                    # (B, D) float
    u_half: jax.Array,                # (2T+1, Du) shared or (B, 2T+1, Du)
    weights: Sequence[jax.Array],
    biases: Sequence[jax.Array],
    dt: float,
    *,
    batch_tile: int = 64,
    time_chunk: int | None = None,
    interpret: bool | None = None,
    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
    precision: str | None = None,
    field=None,
) -> jax.Array:
    """Full-trajectory RK4 solve; returns (T+1, B, D) at the policy's
    storage dtype.  See module doc.

    ``field`` is the in-kernel vector field (:class:`MLPField` of the
    given layers by default, or :class:`CDEField`); ``weights`` and
    ``biases`` are its layers as the source orders them, which
    ``field.stage`` lays out for the kernel.

    ``u_half`` is the drive sampled at RK4 half-steps: (2T+1, Du) shared
    by the whole batch, or (B, 2T+1, Du) with one stimulus per batch
    element (fleet serving); Du may be 0 (autonomous).  ``time_chunk``
    bounds how many RK4 steps stay VMEM-resident per grid cell (None =
    auto-pick the largest chunk fitting ``vmem_budget_bytes``), so the
    horizon T is unbounded.  ``interpret=None`` auto-detects: compiled on
    TPU, interpreter elsewhere (``REPRO_FORCE_INTERPRET`` overrides).
    ``precision`` picks the mixed-precision policy ("f32" | "bf16" |
    "bf16_f32acc"; ``None`` = auto — bf16_f32acc on TPU, f32 elsewhere):
    floating inputs are cast to the policy dtypes here, non-floating
    inputs raise a named ``ValueError`` instead of an opaque Mosaic
    lowering error.
    """
    if interpret is None:
        interpret = default_interpret()
    precision = resolve_precision(precision)
    store, _, _, carry = precision_dtypes(precision)
    _require_float("y0", y0, precision)
    _require_float("u_half", u_half, precision)
    for li, (w, b) in enumerate(zip(weights, biases)):
        _require_float(f"weights[{li}]", w, precision)
        _require_float(f"biases[{li}]", b, precision)
    if field is None:
        field = MLPField(len(weights))
    plan = rollout_plan(*y0.shape, u_half.shape, weights, biases,
                        batch_tile=batch_tile, time_chunk=time_chunk,
                        vmem_budget_bytes=vmem_budget_bytes,
                        precision=precision, field=field)
    C, NC = plan.time_chunk, plan.num_chunks
    weights, biases = field.stage(weights, biases)
    weights = [w.astype(store) for w in weights]
    biases = [b.astype(store) for b in biases]
    u_half = u_half.astype(DRIVE_DTYPE)
    y0 = y0.astype(jnp.float32)       # the seed block; rounded in-kernel
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

    kernel = _make_kernel(field, C, float(dt), du, bt, per_tile_drive,
                          precision)

    grid = (B // bt, NC)                 # time minor: chunks run in order
    u_in, u_spec = chunked_drive_operand(u_half, T, C, NC, bt,
                                         per_tile_drive, reverse=False)
    in_specs = [
        pl.BlockSpec((bt, D), lambda i, j: (i, 0)),      # y0
        u_spec,                                          # u_chunks
    ]
    for w in weights:
        in_specs.append(pl.BlockSpec(w.shape, lambda i, j: (0, 0)))
    for b in biases:
        in_specs.append(pl.BlockSpec(b.shape, lambda i, j: (0,)))
    out_spec = pl.BlockSpec((C, bt, D), lambda i, j: (j, i, 0))

    steps = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((NC * C, B, D), store),
        scratch_shapes=[pltpu.VMEM((bt, D), carry)],
        name="fused_fwd",
        interpret=interpret,
    )(y0, u_in, *weights, *biases)
    # Row k of ``steps`` is y after step k; prepend y0, drop the padded
    # tail of a partial final chunk.
    return jnp.concatenate([y0[None].astype(store), steps[:T]], axis=0)
