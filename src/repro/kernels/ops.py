"""Public jit'd wrappers around the Pallas kernels.

Each op pairs a TPU-target kernel (validated in interpret mode on CPU)
with its pure-jnp oracle in :mod:`repro.kernels.ref`.  Gradient support:
both hot-path ops are differentiable on the kernel substrate itself —
the fused neural-ODE rollout through a reverse-time checkpoint/replay
Pallas kernel (:mod:`repro.kernels.fused_ode_mlp_bwd`), and soft-DTW
through the closed-form E-matrix reverse DP as a second wavefront
kernel (no autodiff of the reference DP anywhere).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.analogue import AnalogueSpec
from repro.core.losses import BIG, _pairwise_dist
from repro.kernels import ref
from repro.kernels.crossbar_vmm import crossbar_matmul as _crossbar_pallas
from repro.kernels.fused_analogue import (
    fused_analogue_rollout as _fused_analogue)
from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET,
                                         _require_float, drive_window,
                                         fused_node_rollout as _fused_pallas,
                                         precision_dtypes,
                                         resolve_precision)
from repro.kernels.fused_ode_mlp_bwd import fused_node_rollout_vjp
from repro.kernels.softdtw import (softdtw_bwd_pallas as _softdtw_bwd_pallas,
                                   softdtw_pallas as _softdtw_pallas)


# ---------------------------------------------------------------------------
# Fused neural-ODE rollout
# ---------------------------------------------------------------------------

def fused_node_rollout(params: Sequence[dict], y0: jax.Array,
                       u_half: jax.Array, dt: float,
                       *, batch_tile: int = 64,
                       time_chunk: int | None = None,
                       interpret: bool | None = None,
                       vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
                       gradient: str = "fused_vjp",
                       precision: str | None = None,
                       ) -> jax.Array:
    """Solve the twin's neural ODE with the weights-stationary kernel.

    The whole RK4 trajectory runs inside one ``pallas_call`` with the MLP
    weights pinned in VMEM (grid layout and VMEM model:
    ``docs/kernels.md``).  Requires a uniform time grid (``dt`` and the
    step count are kernel compile-time constants).

    Args:
      params: the core MLP param list ``[{'w','b'}, ...]``.
      y0: (B, D) initial conditions — one row per fleet member.
      u_half: drive sampled at RK4 half-steps (:func:`half_step_drive`) —
        (2T+1, Du) shared across the batch, (B, 2T+1, Du) per-twin
        (fleet serving), or (2T+1, 0) when autonomous.
      dt: RK4 step size (uniform).
      batch_tile: fleet members per grid cell; B must divide by it
        (``FusedPallasBackend`` pads the fleet up to a tile multiple).
      time_chunk: RK4 steps resident in VMEM per grid cell.  ``None``
        auto-picks the largest chunk whose working set fits
        ``vmem_budget_bytes`` (see ``fused_ode_mlp.plan_time_chunk``), so
        the horizon T is unbounded; an explicit value is validated
        against the same budget.
      interpret: ``None`` auto-detects the accelerator (compiled on TPU,
        interpreter on CPU/GPU hosts); pass True/False to force.
      vmem_budget_bytes: the planner's per-cell VMEM budget.  If the
        weights plus a single RK4 step cannot fit, a ``ValueError`` is
        raised at planning time ("shrink batch_tile or the MLP").
      gradient: ``"fused_vjp"`` (default) makes the rollout
        differentiable in ``params`` and ``y0`` through the reverse-time
        checkpoint/replay kernel (:mod:`repro.kernels.fused_ode_mlp_bwd`)
        — the drive is data and gets a zero cotangent; ``"stopgrad"``
        detaches the solve (inference-only serving).
      precision: mixed-precision policy — ``"f32"`` | ``"bf16"`` |
        ``"bf16_f32acc"``, or ``None`` for the platform default
        (bf16_f32acc on TPU, f32 elsewhere).  The bf16 policies store
        weights and trajectory slabs at half width while matmuls
        accumulate at f32 (``bf16_f32acc``) and gradient accumulators
        always stay f32; the error model is documented in
        ``docs/kernels.md``.  Non-floating inputs raise a ``ValueError``
        naming the offending input.

    Returns:
      The (T+1, B, D) trajectory (y0 prepended), at the policy's
      storage dtype.
    """
    precision = resolve_precision(precision)
    named = [("y0", y0), ("u_half", u_half)]
    named += [(f"params[{i}]['w']", p["w"]) for i, p in enumerate(params)]
    named += [(f"params[{i}]['b']", p["b"]) for i, p in enumerate(params)]
    for name, x in named:      # fail HERE with the dict-level input name,
        _require_float(name, x, precision)  # not inside the kernel wrapper
    # hand the kernels f32 master copies; the precision policy decides
    # (inside the kernel wrappers) what is rounded to storage width, so
    # cotangents come back at f32 regardless of the substrate dtype
    weights = [p["w"].astype(jnp.float32) for p in params]
    biases = [p["b"].astype(jnp.float32) for p in params]
    y0 = y0.astype(jnp.float32)
    u_half = u_half.astype(jnp.float32)
    if gradient == "fused_vjp":
        return fused_node_rollout_vjp(y0, u_half, weights, biases,
                                      float(dt), batch_tile, time_chunk,
                                      interpret, vmem_budget_bytes,
                                      precision)
    if gradient == "stopgrad":
        out = _fused_pallas(lax.stop_gradient(y0),
                            lax.stop_gradient(u_half),
                            [lax.stop_gradient(w) for w in weights],
                            [lax.stop_gradient(b) for b in biases],
                            float(dt),
                            batch_tile=batch_tile, time_chunk=time_chunk,
                            interpret=interpret,
                            vmem_budget_bytes=vmem_budget_bytes,
                            precision=precision)
        return lax.stop_gradient(out)
    raise ValueError(
        f"unknown gradient mode {gradient!r}; have 'fused_vjp', 'stopgrad'")


def fused_node_rollout_ref(params, y0, u_half, dt):
    weights = [p["w"].astype(jnp.float32) for p in params]
    biases = [p["b"].astype(jnp.float32) for p in params]
    return ref.fused_node_rollout_ref(y0.astype(jnp.float32),
                                      u_half.astype(jnp.float32),
                                      weights, biases, float(dt))


def half_step_drive(drive, ts: jax.Array) -> jax.Array:
    """Sample a continuous drive u(t) at the RK4 half-step grid (2T+1, 1)."""
    t0, t1 = ts[0], ts[-1]
    T = ts.shape[0] - 1
    th = jnp.linspace(t0, t1, 2 * T + 1)
    u = jax.vmap(drive)(th)
    return u[:, None] if u.ndim == 1 else u


# ---------------------------------------------------------------------------
# Canonical global time grids (the streaming-resume determinism contract)
# ---------------------------------------------------------------------------
#
# A rollout resumed at global step k is only bit-identical to the
# uninterrupted one if every time value it sees is BYTE-identical to the
# value the uninterrupted rollout saw.  Re-deriving a sub-window with
# ``linspace(t_k, t_T, ...)`` perturbs interior points by ~1 ulp (f32
# endpoints, divided differently), which is enough to move every drive
# sample and break parity.  These helpers are the single source of truth:
# each grid point is an exact float64 function of (t0, dt, global index),
# rounded to float32 once — so any window of any split reproduces the
# same bytes.  ``start_step`` may be an int or an (N,) array of per-twin
# offsets (rows of the result are then per-twin windows).

def window_times(t0: float, dt: float, num_steps: int,
                 start_step=0) -> jax.Array:
    """The (num_steps+1,) f32 time grid t_i = t0 + dt*(start_step + i),
    computed in float64; (N, num_steps+1) for an (N,) ``start_step``."""
    start = np.asarray(start_step, dtype=np.int64)
    idx = start[..., None] + np.arange(num_steps + 1, dtype=np.int64)
    t = np.float64(t0) + np.float64(dt) * idx
    return jnp.asarray(t.astype(np.float32))


def half_step_times(t0: float, dt: float, num_steps: int,
                    start_step=0) -> jax.Array:
    """The (2*num_steps+1,) f32 RK4 half-step grid
    t_j = t0 + (dt/2)*(2*start_step + j), computed in float64;
    (N, 2*num_steps+1) for an (N,) ``start_step``."""
    start = np.asarray(start_step, dtype=np.int64)
    idx = 2 * start[..., None] + np.arange(2 * num_steps + 1, dtype=np.int64)
    t = np.float64(t0) + 0.5 * np.float64(dt) * idx
    return jnp.asarray(t.astype(np.float32))


def sample_drive_window(drive, t0: float, dt: float, num_steps: int,
                        start_step=0) -> jax.Array:
    """Sample u(t) on the canonical half-step window: (2T'+1, Du) for a
    scalar ``start_step``, (N, 2T'+1, Du) per-twin for an (N,) one."""
    th = half_step_times(t0, dt, num_steps, start_step)
    u = jax.vmap(drive)(th) if th.ndim == 1 else jax.vmap(jax.vmap(drive))(th)
    return u[..., None] if u.ndim == th.ndim else u


# ---------------------------------------------------------------------------
# Crossbar VMM
# ---------------------------------------------------------------------------

def _require_2d_float(op: str, name: str, x: jax.Array) -> None:
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"{op}: {name} must be 2-D, got shape {x.shape}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(
            f"{op}: {name} has non-floating dtype {x.dtype}; cast it to "
            f"a floating dtype first")


def _fault_kernel_kwargs(fault: dict | None, spec: AnalogueSpec,
                         layer: int) -> dict:
    """Translate a ``FaultModel.kernel_args()`` dict into the static
    scalars of :func:`repro.kernels.crossbar_vmm.crossbar_matmul`: the
    per-(layer, pair) stuck salts of the core convention, plus the drift
    snapshot factor — a single VMM has a fixed read count, so the
    power-law decay collapses to one static multiplier here (only the
    fused rollout kernel advances it live)."""
    if not fault:
        return {}
    drift = 1.0
    if fault.get("drift_nu", 0.0) > 0.0:
        drift = (1.0 + fault.get("drift_n0", 0)
                 / fault["drift_tau"]) ** (-fault["drift_nu"])
    base = fault.get("salt_base", 0)
    return {
        "stuck_rate": fault.get("stuck_rate", 0.0),
        "stuck_on_frac": fault.get("stuck_on_frac", 0.5),
        "fault_seed": fault.get("fault_seed", 0),
        "fault_salts": (base + 2 * layer, base + 2 * layer + 1),
        "drift": drift,
        "g_max": spec.g_max,
    }


def crossbar_vmm(prog: dict, x: jax.Array, spec: AnalogueSpec,
                 *, interpret: bool | None = None,
                 read_noise: float | None = None,
                 noise_seed: int = 0,
                 fault: dict | None = None,
                 layer: int = 0) -> jax.Array:
    """Analogue crossbar read through the fused kernel (float mode).

    ``interpret=None`` auto-detects (compiled on TPU, interpreter
    elsewhere; ``REPRO_FORCE_INTERPRET`` pins the mode).  ``read_noise``
    overrides ``spec.read_noise`` (None = take the spec's value) with
    the deterministic counter-derived stream keyed on ``noise_seed``.
    ``fault`` (a ``FaultModel.kernel_args()`` dict) injects stuck cells
    and a drift snapshot in-kernel at the device array addressed by
    ``layer`` — bitwise the program-time masks of
    :mod:`repro.core.faults`, at zero extra HBM traffic.
    """
    _require_2d_float("crossbar_vmm", "x", x)
    _require_2d_float("crossbar_vmm", "prog['gp']", prog["gp"])
    _require_2d_float("crossbar_vmm", "prog['gm']", prog["gm"])
    sigma = spec.read_noise if read_noise is None else read_noise
    # scale is traced (programming may run under jit), so the rescale —
    # and therefore the clamp, which acts in post-scale units — happens
    # outside the kernel here; the fused rollout kernel, whose scales
    # ride in as an operand, clamps in-kernel.
    y = _crossbar_pallas(
        x, prog["gp"], prog["gm"],
        inv_scale=1.0, g_step=None, clamp=None,
        read_noise=float(sigma), noise_seed=noise_seed,
        g_min=spec.g_min, interpret=interpret,
        **_fault_kernel_kwargs(fault, spec, layer)) / prog["scale"]
    if spec.v_clamp is not None:
        y = jnp.clip(y, -spec.v_clamp, spec.v_clamp)
    return y


def crossbar_vmm_quantized(x: jax.Array, gp_idx: jax.Array,
                           gm_idx: jax.Array, spec: AnalogueSpec,
                           scale: jax.Array | float,
                           *, interpret: bool | None = None,
                           read_noise: float | None = None,
                           noise_seed: int = 0,
                           fault: dict | None = None,
                           layer: int = 0) -> jax.Array:
    """Quantised-storage read: uint8 level indices, dequant fused in-kernel.

    Same interpret auto-detect, noise and fault contract as
    ``crossbar_vmm``; noisy or faulty reads reconstruct the absolute
    conductances from ``spec.g_min`` in-kernel (the differential offsets
    only cancel clean, and stuck cells pin to absolute G_on/G_off).
    """
    _require_2d_float("crossbar_vmm_quantized", "x", x)
    for name, idx in (("gp_idx", gp_idx), ("gm_idx", gm_idx)):
        idx = jnp.asarray(idx)
        if idx.ndim != 2 or idx.dtype != jnp.uint8:
            raise ValueError(
                f"crossbar_vmm_quantized: {name} must be 2-D uint8 level "
                f"indices, got shape {idx.shape} dtype {idx.dtype}")
    sigma = spec.read_noise if read_noise is None else read_noise
    g_step = (spec.g_max - spec.g_min) / (spec.levels - 1)
    y = _crossbar_pallas(x, gp_idx, gm_idx, inv_scale=1.0,
                         g_step=float(g_step), clamp=None,
                         read_noise=float(sigma), noise_seed=noise_seed,
                         g_min=spec.g_min, interpret=interpret,
                         **_fault_kernel_kwargs(fault, spec, layer)) / scale
    if spec.v_clamp is not None:
        y = jnp.clip(y, -spec.v_clamp, spec.v_clamp)
    return y


def fused_analogue_rollout(staged: dict, y0: jax.Array, u_half: jax.Array,
                           dt: float, *, batch_tile: int = 64,
                           time_chunk: int | None = None,
                           interpret: bool | None = None,
                           vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET,
                           read_noise: float = 0.0,
                           noise_seed: int = 0,
                           step_offset: int = 0) -> jax.Array:
    """Whole-trajectory analogue RK4 solve on the fused crossbar kernel.

    ``staged`` is the deployment dict built by
    ``FusedAnalogueBackend.program`` (or assembled by hand):

      gps, gms — per-layer (K_l+1, N_l) conductance pairs, float32 or
                 uint8 level indices (bias folded as the last row);
      scales   — (L,) per-tensor programming scales;
      g_step   — dequant step for uint8 storage (None = float);
      g_min    — conductance floor (needed for noisy quantised reads);
      g_max    — conductance ceiling (needed for stuck-cell injection);
      v_clamp  — optional peripheral output clamp;
      fault    — optional ``FaultModel.kernel_args()`` dict: stuck cells
                 and live read-disturb drift injected in-kernel (see
                 :mod:`repro.core.faults`).

    The solve is inference-only (the analogue substrate does not
    backpropagate — train digitally, deploy analogue): all inputs are
    detached and the trajectory returns with zero cotangent.  See
    :mod:`repro.kernels.fused_analogue` for the kernel itself and the
    deterministic read-noise stream; ``step_offset`` (the global step
    index of ``y0``) makes a resumed noisy/drifting rollout replay the
    uninterrupted rollout's noise salts and drift exponents.
    """
    _require_2d_float("fused_analogue_rollout", "y0", y0)
    if not jnp.issubdtype(jnp.asarray(u_half).dtype, jnp.floating):
        raise ValueError(
            f"fused_analogue_rollout: u_half has non-floating dtype "
            f"{jnp.asarray(u_half).dtype}; cast it to a floating dtype")
    out = _fused_analogue(
        [lax.stop_gradient(g) for g in staged["gps"]],
        [lax.stop_gradient(g) for g in staged["gms"]],
        lax.stop_gradient(jnp.asarray(staged["scales"])),
        lax.stop_gradient(y0), lax.stop_gradient(u_half), float(dt),
        g_step=staged.get("g_step"), g_min=staged.get("g_min", 0.0),
        g_max=staged.get("g_max", 0.0), fault=staged.get("fault"),
        v_clamp=staged.get("v_clamp"), read_noise=float(read_noise),
        noise_seed=int(noise_seed), step_offset=int(step_offset),
        batch_tile=batch_tile,
        time_chunk=time_chunk, interpret=interpret,
        vmem_budget_bytes=vmem_budget_bytes)
    return lax.stop_gradient(out)


def quantize_to_levels(w: jax.Array, spec: AnalogueSpec):
    """Map weights to (gp_idx, gm_idx, scale) uint8 level tensors."""
    from repro.core.analogue import conductance_pair
    gp, gm, scale = conductance_pair(w, spec)
    step = (spec.g_max - spec.g_min) / (spec.levels - 1)
    to_idx = lambda g: jnp.clip(jnp.round((g - spec.g_min) / step),
                                0, spec.levels - 1).astype(jnp.uint8)
    return to_idx(gp), to_idx(gm), scale


# ---------------------------------------------------------------------------
# soft-DTW (kernel forward, kernelised E-matrix backward)
# ---------------------------------------------------------------------------

def _diag_layout_batch(D: jax.Array, chunk: int) -> jax.Array:
    dd = jax.vmap(ref.diag_layout)(D)
    kd = dd.shape[1]
    pad = (-kd) % chunk
    if pad:
        dd = jnp.pad(dd, ((0, 0), (0, pad), (0, 0)), constant_values=BIG)
    return dd


def _undiag_batch(e_dd: jax.Array, n: int, m: int) -> jax.Array:
    """Inverse of ``ref.diag_layout``: (B, KD_pad, n) -> (B, n, m)."""
    rows = jnp.arange(n)[:, None]
    cols = jnp.arange(m)[None, :]
    return e_dd[:, rows + cols, rows]


def _sdtw_chunk(n: int, m: int) -> int:
    return min(256, n + m - 1)


def _sdtw_cost_slab(x, y, chunk, precision):
    """Diagonal-layout cost slab at the policy's storage dtype (bf16
    halves the only O(n·m) operand; carries/outputs stay f32)."""
    D = jax.vmap(_pairwise_dist)(x, y)
    store = precision_dtypes(resolve_precision(precision))[0]
    return _diag_layout_batch(D, chunk).astype(store)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def soft_dtw(x: jax.Array, y: jax.Array, gamma: float = 1.0,
             interpret: bool | None = None,
             precision: str | None = None) -> jax.Array:
    """Batched soft-DTW((B,n,d),(B,m,d)) -> (B,) via the wavefront kernel.

    ``precision``: ``"f32"`` | ``"bf16"`` | ``"bf16_f32acc"`` (``None``
    = platform default).  Under the bf16 policies the cost matrix
    streams through the kernel at bfloat16 while the R/E diagonal
    carries and the answer stay float32 (see ``docs/kernels.md``).
    ``interpret=None`` auto-detects (compiled on TPU).
    """
    n, m = x.shape[1], y.shape[1]
    chunk = _sdtw_chunk(n, m)
    dd = _sdtw_cost_slab(x, y, chunk, precision)
    return _softdtw_pallas(dd, n, m, gamma=gamma, hard=False, chunk=chunk,
                           interpret=interpret)


def _sdtw_fwd(x, y, gamma, interpret, precision):
    n, m = x.shape[1], y.shape[1]
    chunk = _sdtw_chunk(n, m)
    dd = _sdtw_cost_slab(x, y, chunk, precision)
    ans, rd = _softdtw_pallas(dd, n, m, gamma=gamma, hard=False, chunk=chunk,
                              interpret=interpret, return_r=True)
    # residuals: only R must come from the forward kernel; the cost slab
    # is cheaply re-derived from (x, y) in the backward
    return ans, (x, y, rd)


def _sdtw_bwd(gamma, interpret, precision, res, g):
    # Closed-form E-matrix reverse DP as a second wavefront kernel
    # (kernels/softdtw.py) — dSDTW/dD = E, then an elementwise pullback
    # through the |x_i - y_j| cost.  The old autodiff-of-the-reference-DP
    # path (O(n·m) sequential tape) is gone.
    x, y, rd = res
    n, m = x.shape[1], y.shape[1]
    chunk = _sdtw_chunk(n, m)
    store = precision_dtypes(resolve_precision(precision))[0]
    D, dist_vjp = jax.vjp(lambda a, b: jax.vmap(_pairwise_dist)(a, b), x, y)
    dd = _diag_layout_batch(D, chunk).astype(store)
    e_dd = _softdtw_bwd_pallas(dd, rd, n, m, gamma=gamma, chunk=chunk,
                               interpret=interpret)
    dD = g[:, None, None] * _undiag_batch(e_dd, n, m)
    gx, gy = dist_vjp(dD.astype(D.dtype))
    return gx, gy


soft_dtw.defvjp(_sdtw_fwd, _sdtw_bwd)


def dtw_distance(x: jax.Array, y: jax.Array, *,
                 interpret: bool | None = None) -> jax.Array:
    """Batched hard-DTW metric via the same wavefront kernel."""
    D = jax.vmap(_pairwise_dist)(x, y)
    n, m = D.shape[1], D.shape[2]
    chunk = min(256, n + m - 1)
    dd = _diag_layout_batch(D, chunk)
    return _softdtw_pallas(dd, n, m, gamma=1.0, hard=True, chunk=chunk,
                           interpret=interpret)
