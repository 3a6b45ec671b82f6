"""Training loops for the continuous-time digital twins.

Faithful to the paper's Methods: Adam, RK4 ODESolve, adjoint-state
gradients, (soft-)DTW or L1 objectives, and random state noise as a
regulariser during training (their ref. 46).  Two practical additions,
both documented in EXPERIMENTS.md:

* multiple-shooting segmentation — the trajectory is split into segments
  that are solved in parallel from ground-truth initial states (vmap over
  segments).  This is the standard stabiliser for chaotic NODE training
  and maps perfectly onto batched TPU execution.
* derivative-matching warm start — regress f_theta(x) onto finite-
  difference derivatives before trajectory training (a cheap collocation
  pretraining that cuts trajectory epochs ~10x).

The trajectory loss is substrate-selectable (``segment_loss_fn``'s
``backend=``): the default digital path vmaps one adjoint solve per
shooting segment, while ``backend="fused_pallas"`` batches all segments
through the weights-stationary Pallas kernel and differentiates through
its reverse-time checkpoint/replay VJP — training on the substrate that
serves.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.losses import l1, soft_dtw
from repro.train.optimizer import Optimizer, apply_updates

Pytree = Any


def _step_body(loss_fn: Callable, optimizer: Optimizer, has_key: bool,
               params, opt_state, key, step=None):
    """One descent step — the shared body of both training engines.

    ``step`` (a traced int32, threaded only for losses that declare
    ``loss_fn.wants_step = True``) is the global step counter that keys
    the hardware-aware device-model noise draws — see
    :mod:`repro.train.hw_aware`."""
    if has_key:
        key, sub = jax.random.split(key)
    else:
        sub = None
    if step is None:
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, sub))(params)
    else:
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, sub, step))(params)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = apply_updates(params, updates)
    return params, opt_state, key, loss


def _wants_step(loss_fn: Callable) -> bool:
    """Does the loss want the global step counter as a third argument?

    Step-keyed losses (hardware-aware training) opt in by setting
    ``loss_fn.wants_step = True``; the engines then carry an int32 step
    counter through the scan and call ``loss_fn(params, key, step)``.
    Plain losses keep the exact legacy engine signatures."""
    return bool(getattr(loss_fn, "wants_step", False))


def make_step_fn(loss_fn: Callable, optimizer: Optimizer,
                 has_key: bool) -> Callable:
    """Jitted single step: (params, opt_state, key) -> same + loss.

    The per-step engine — one device dispatch per optimisation step.
    Kept as the reference implementation for :func:`fit_per_step` and the
    ``train_throughput`` benchmark baseline.  For step-keyed losses
    (``loss_fn.wants_step``) the signature gains a trailing int32 step
    counter: (params, opt_state, key, step) -> same + loss."""

    if _wants_step(loss_fn):
        @jax.jit
        def step_keyed(params, opt_state, key, step):
            params, opt_state, key, loss = _step_body(
                loss_fn, optimizer, has_key, params, opt_state, key,
                step=step)
            return params, opt_state, key, step + jnp.int32(1), loss

        return step_keyed

    @jax.jit
    def step(params, opt_state, key):
        return _step_body(loss_fn, optimizer, has_key, params, opt_state, key)

    return step


def make_scan_engine(loss_fn: Callable, optimizer: Optimizer, has_key: bool,
                     donate: bool = False, unroll: int = 8) -> Callable:
    """Scan-compiled engine: (params, opt_state, key, n) -> carries + losses.

    Runs ``n`` optimisation steps as one ``lax.scan`` inside a single jit,
    so the host dispatches once per *chunk* instead of once per step —
    the paper-sized MLPs are otherwise dominated by host round-trips.
    ``n`` is static (at most two compilations per fit: full chunk +
    remainder).  ``donate=True`` donates the (params, opt_state) carry
    buffers to the chunk (in-place on accelerators; ignored on CPU).
    ``unroll`` unrolls the scan body (same ops in the same order — purely
    a loop-overhead optimisation for the tiny paper-sized step bodies).

    For step-keyed losses (``loss_fn.wants_step``, hardware-aware
    training) the engine is instead
    (params, opt_state, key, step0, n) -> carries + step0 + losses —
    the int32 global step counter rides in the scan carry so the chunk
    remains ONE jit and every device-model draw is keyed by the absolute
    step, independent of chunking.
    """

    if _wants_step(loss_fn):
        def scan_step_keyed(carry, _):
            params, opt_state, key, si = carry
            params, opt_state, key, loss = _step_body(
                loss_fn, optimizer, has_key, params, opt_state, key, step=si)
            return (params, opt_state, key, si + jnp.int32(1)), loss

        @functools.partial(jax.jit, static_argnums=4,
                           donate_argnums=(0, 1) if donate else ())
        def run_chunk_keyed(params, opt_state, key, step0, n):
            carry = (params, opt_state, key, jnp.asarray(step0, jnp.int32))
            (params, opt_state, key, step0), losses = lax.scan(
                scan_step_keyed, carry, None, length=n,
                unroll=min(unroll, n))
            return params, opt_state, key, step0, losses

        return run_chunk_keyed

    def scan_step(carry, _):
        params, opt_state, key = carry
        params, opt_state, key, loss = _step_body(
            loss_fn, optimizer, has_key, params, opt_state, key)
        return (params, opt_state, key), loss

    @functools.partial(jax.jit, static_argnums=3,
                       donate_argnums=(0, 1) if donate else ())
    def run_chunk(params, opt_state, key, n):
        (params, opt_state, key), losses = lax.scan(
            scan_step, (params, opt_state, key), None, length=n,
            unroll=min(unroll, n))
        return params, opt_state, key, losses

    return run_chunk


def fit(loss_fn: Callable, params: Pytree, optimizer: Optimizer,
        num_steps: int, key: jax.Array | None = None,
        log_every: int = 0,
        scan_chunk: int | None = None) -> tuple[Pytree, jax.Array]:
    """Generic scan-compiled full-batch descent; loss_fn(params, key) -> scalar.

    The training loop is a ``lax.scan`` over chunks of ``scan_chunk``
    steps inside one jit with donated (params, opt_state) carries; the
    host syncs only at chunk boundaries, where the chunk's stacked loss
    history comes back as one array (logging reads from it — there is no
    per-step ``float(loss)`` device sync).

    Knobs:
      ``scan_chunk`` — steps per compiled chunk.  ``None`` runs all
      ``num_steps`` in a single chunk when not logging, or chunks at the
      logging cadence otherwise; at most two compilations ever happen
      (full chunk + remainder).
      ``unroll`` — the scan body is unrolled 8× inside the chunk (see
      :func:`make_scan_engine`'s ``unroll`` parameter): same ops in the
      same order, purely loop-overhead amortisation for the tiny
      paper-sized step bodies.

    Numerics are step-for-step identical to the per-step reference loop:
    :func:`fit_per_step` is kept as the equivalence oracle
    (``tests/test_trainer.py`` pins scan ≡ per-step across chunkings,
    optimizers and keyless losses) and as the ``train_throughput``
    benchmark baseline the scan engine is ratio-gated against.

    Returns ``(params, losses)`` with ``losses`` the full (num_steps,)
    loss history.
    """
    opt_state = optimizer.init(params)
    if num_steps <= 0:
        return params, jnp.zeros((0,), jnp.float32)
    if scan_chunk is None:
        scan_chunk = num_steps if not log_every else max(log_every, 100)
    scan_chunk = max(1, min(scan_chunk, num_steps))

    donate = jax.default_backend() != "cpu"
    if donate:
        # keep the caller's buffers alive — only fit-internal carries are
        # donated chunk to chunk
        params = jax.tree_util.tree_map(jnp.copy, params)
        opt_state = jax.tree_util.tree_map(jnp.copy, opt_state)
    run_chunk = make_scan_engine(loss_fn, optimizer, key is not None,
                                 donate=donate)
    wants_step = _wants_step(loss_fn)
    step0 = jnp.asarray(0, jnp.int32)

    chunks, done = [], 0
    while done < num_steps:
        n = min(scan_chunk, num_steps - done)
        if wants_step:
            params, opt_state, key, step0, losses = run_chunk(
                params, opt_state, key, step0, n)
        else:
            params, opt_state, key, losses = run_chunk(
                params, opt_state, key, n)
        if log_every:
            hist = np.asarray(losses)       # one host sync per chunk
            for t in range(n):
                i = done + t
                if i % log_every == 0 or i == num_steps - 1:
                    print(f"  step {i:5d}  loss {hist[t]:.6f}")
        chunks.append(losses)
        done += n
    return params, jnp.concatenate(chunks)


def fit_per_step(loss_fn: Callable, params: Pytree, optimizer: Optimizer,
                 num_steps: int, key: jax.Array | None = None,
                 log_every: int = 0) -> tuple[Pytree, jax.Array]:
    """Reference per-step loop (one jitted dispatch per step).

    Superseded by the scan-compiled :func:`fit` on every hot path; kept
    as the equivalence oracle and the ``train_throughput`` baseline.
    """
    opt_state = optimizer.init(params)
    step = make_step_fn(loss_fn, optimizer, key is not None)
    wants_step = _wants_step(loss_fn)
    si = jnp.asarray(0, jnp.int32)
    losses = []
    for i in range(num_steps):
        if wants_step:
            params, opt_state, key, si, loss = step(params, opt_state,
                                                    key, si)
        else:
            params, opt_state, key, loss = step(params, opt_state, key)
        losses.append(loss)
        if log_every and (i % log_every == 0 or i == num_steps - 1):
            print(f"  step {i:5d}  loss {float(loss):.6f}")
    return params, jnp.stack(losses) if losses else jnp.zeros((0,))


# ---------------------------------------------------------------------------
# Multiple-shooting segmentation
# ---------------------------------------------------------------------------

def make_segments(ts: jax.Array, ys: jax.Array, segment_len: int):
    """Split (T,)/(T,D) into overlapping shooting segments.

    Returns (ts_seg (S, L+1), ys_seg (S, L+1, D)) where consecutive
    segments share their boundary point.
    """
    T = ts.shape[0]
    L = segment_len
    S = (T - 1) // L
    idx = jnp.arange(S)[:, None] * L + jnp.arange(L + 1)[None, :]
    return ts[idx], ys[idx]


def _segment_objective(loss: str, gamma: float, preds, ys_seg,
                       kernelised: bool = False, interpret=None,
                       precision=None):
    """Shared loss combinators over (S, L+1, D) predictions/targets.

    ``kernelised=True`` (the fused training path) routes soft-DTW through
    the wavefront Pallas kernels — forward AND the closed-form E-matrix
    backward — instead of the pure-jnp reference DP; ``precision``
    threads the backend's mixed-precision policy into the soft-DTW cost
    slab (the R/E carries stay f32 — see ``docs/kernels.md``)."""
    preds = preds.astype(jnp.float32)      # bf16 rollouts meet f32 targets
    if kernelised and loss != "l1":
        from repro.kernels import ops
        sdtw = jnp.mean(ops.soft_dtw(preds, ys_seg, gamma, interpret,
                                     precision))
    elif loss != "l1":
        per_seg = jax.vmap(lambda p, t: soft_dtw(p, t, gamma))(preds, ys_seg)
        sdtw = jnp.mean(per_seg)
    if loss == "l1":
        return l1(preds, ys_seg)
    if loss == "softdtw":
        return sdtw / ys_seg.shape[1]
    if loss == "l1+softdtw":
        return l1(preds, ys_seg) + 0.1 * sdtw / ys_seg.shape[1]
    raise ValueError(loss)


def _fused_segment_loss_fn(twin, backend, ts_seg, ys_seg, loss: str,
                           gamma: float, noise_std: float, hw_aware=None):
    """Multiple-shooting loss on the fused-Pallas substrate.

    The segments become the kernel's BATCH dimension: one grid-tiled
    weights-stationary solve integrates all S shooting segments at once
    (for a driven twin each segment gets its own drive slab, sampled at
    its absolute half-step times — the per-tile-drive kernel path), and
    the reverse-time kernel carries the gradients.  Differs from the
    digital vmap path only by the substrate; the objective, segmentation
    and noise regularisation are identical.

    ``hw_aware`` (an :class:`repro.train.hw_aware.HwAwareConfig`) makes
    the loss hardware-aware: each evaluation passes ``params`` through
    the analogue write path (STE quantise + programming/read noise +
    optional faults, keyed by the global training step) before the fused
    rollout, averaged over ``k_draws`` device realisations.  The device
    model is a weight-space pre-transform, so the reverse-time VJP kernel
    is untouched; the returned loss sets ``wants_step`` so the engines
    thread the step counter.
    """
    from repro.kernels import ops
    from repro.kernels.fused_ode_mlp import pad_fleet_to_tile

    # honour the twin's solver config: RK4 only (as the serving backend
    # enforces), with steps_per_interval densifying each segment's grid
    method = getattr(twin.node, "method", "rk4")
    if method != "rk4":
        raise ValueError(
            f"fused-backend training integrates RK4 only, got {method!r}")
    sub = int(getattr(twin.node, "steps_per_interval", 1))

    S, Lp1 = ts_seg.shape[0], ts_seg.shape[1]
    tsn = np.asarray(ts_seg, dtype=np.float64)
    # uniformity judged on the VALUES (float32 diffs wobble by ~eps*t),
    # mirroring FusedPallasBackend._grid: every segment must sit on one
    # shared-dt line starting at its own offset
    dt = float(np.mean(tsn[:, -1] - tsn[:, 0]) / (Lp1 - 1))
    drift = np.abs(tsn - (tsn[:, :1] + dt * np.arange(Lp1))).max()
    tol = max(32 * np.finfo(np.float32).eps * np.abs(tsn).max(), 1e-9)
    if dt == 0 or drift > tol:
        raise ValueError(
            "fused-backend training needs a uniform time grid (shared dt "
            "across all shooting segments)")
    T_fine = (Lp1 - 1) * sub
    drive = getattr(twin.field, "drive", None)
    if drive is None:
        uh = jnp.zeros((2 * T_fine + 1, 0), jnp.float32)
    else:
        # per-segment drive sampled at each segment's absolute (fine) times
        uh = jax.vmap(lambda row: ops.half_step_drive(
            drive, jnp.linspace(row[0], row[-1], T_fine + 1)))(ts_seg)
        uh = uh.astype(jnp.float32)

    def loss_fn(params, key, step=None):
        y0s = ys_seg[:, 0]
        if noise_std > 0 and key is not None:
            y0s = y0s + noise_std * jax.random.normal(key, y0s.shape)
        # pad segments up to a tile multiple, as rollout_batch_local does
        y0p, uhp, bt, _ = pad_fleet_to_tile(y0s, uh, backend.batch_tile)

        def rollout_loss(p):
            traj = ops.fused_node_rollout(
                p, y0p, uhp, dt / sub, batch_tile=bt,
                time_chunk=backend.time_chunk, interpret=backend.interpret,
                vmem_budget_bytes=backend.vmem_budget_bytes,
                gradient="fused_vjp", precision=backend.precision)
            preds = jnp.transpose(traj[::sub, :S], (1, 0, 2))  # (S, L+1, D)
            return _segment_objective(loss, gamma, preds, ys_seg,
                                      kernelised=True,
                                      interpret=backend.interpret,
                                      precision=backend.precision)

        if hw_aware is None:
            return rollout_loss(params)
        from repro.train.hw_aware import (expectation_over_draws,
                                          hw_aware_params)
        return expectation_over_draws(
            lambda d: rollout_loss(hw_aware_params(params, hw_aware,
                                                   step, d)),
            hw_aware)

    if hw_aware is not None:
        loss_fn.wants_step = True
    return loss_fn


def segment_loss_fn(twin, ts_seg, ys_seg, loss: str = "l1",
                    gamma: float = 0.1, noise_std: float = 0.0,
                    backend=None, hw_aware=None):
    """Loss over shooting segments solved in parallel.

    ``backend``: optional execution substrate (Backend instance or
    registry name); ``None`` uses the twin's own backend.  Digital and
    analogue substrates vmap one solve per segment; the fused-Pallas
    substrate batches all segments through one weights-stationary kernel
    with the reverse-time VJP (train where you serve).

    ``hw_aware``: optional :class:`repro.train.hw_aware.HwAwareConfig`
    turning on hardware-aware training — every loss evaluation sees the
    weights through the analogue write path (STE 6-bit quantise +
    programming/read noise + optional fault ensemble), step-keyed and
    bitwise-reproducible.  Works on any differentiable substrate.
    Training directly on an ``analogue_fused``/``FusedAnalogueBackend``
    substrate implies hardware-aware mode: the policy is auto-derived
    from the backend's own spec/faults (``HwAwareConfig.from_backend``)
    and the rollout integrates on the fused digital kernel with the
    device-degraded weights — previously such training silently fell
    through to the clean digital kernel with detached device physics.
    """
    from repro.core.backends import (FusedAnalogueBackend,
                                     FusedPallasBackend, resolve_backend)

    be = resolve_backend(backend) if backend is not None else twin.backend
    if hw_aware is None and isinstance(be, FusedAnalogueBackend):
        from repro.train.hw_aware import HwAwareConfig
        hw_aware = HwAwareConfig.from_backend(be)
    if isinstance(be, FusedPallasBackend):
        return _fused_segment_loss_fn(twin, be, ts_seg, ys_seg, loss,
                                      gamma, noise_std, hw_aware)
    if backend is not None:
        twin = twin.with_backend(be)

    def loss_fn(params, key, step=None):
        y0s = ys_seg[:, 0]
        if noise_std > 0 and key is not None:
            y0s = y0s + noise_std * jax.random.normal(key, y0s.shape)

        def rollout_loss(p):
            preds = jax.vmap(lambda y0, t: twin.simulate(p, y0, t))(
                y0s, ts_seg)
            return _segment_objective(loss, gamma, preds, ys_seg)

        if hw_aware is None:
            return rollout_loss(params)
        from repro.train.hw_aware import (expectation_over_draws,
                                          hw_aware_params)
        return expectation_over_draws(
            lambda d: rollout_loss(hw_aware_params(params, hw_aware,
                                                   step, d)),
            hw_aware)

    if hw_aware is not None:
        loss_fn.wants_step = True
    return loss_fn


def train_twin(twin, params, ts: jax.Array, ys: jax.Array, *,
               optimizer: Optimizer, num_steps: int,
               segment_len: int = 50, loss: str = "l1",
               gamma: float = 0.1, noise_std: float = 0.0,
               key: jax.Array | None = None, log_every: int = 0,
               backend=None, scan_chunk: int | None = None,
               hw_aware=None):
    """Train a twin on one observed trajectory (paper's training setup).

    ``backend`` selects the training substrate (see
    :func:`segment_loss_fn`): ``backend="fused_pallas"`` (or a
    ``FusedPallasBackend`` instance) runs every forward AND backward
    solve through the weights-stationary Pallas kernels.  The backend's
    ``precision`` policy rides along — e.g.
    ``backend=FusedPallasBackend(precision="bf16_f32acc")`` trains on
    the reduced-precision substrate (bf16 slabs, f32 accumulation; the
    loss and optimizer state stay f32).

    ``hw_aware`` (an :class:`repro.train.hw_aware.HwAwareConfig`) trains
    noise-aware weights: every loss evaluation passes ``params`` through
    the analogue write path first — 6-bit quantise-dequantise under a
    straight-through estimator, programming + read noise from the
    kernels' counter-derived stream keyed by the global step, optional
    stuck-cell/drift ensemble — averaged over ``k_draws`` realisations.
    The fit stays one scan-compiled jit; same seed ⇒ bitwise-identical
    loss history.
    """
    ts_seg, ys_seg = make_segments(ts, ys, segment_len)
    loss_fn = segment_loss_fn(twin, ts_seg, ys_seg, loss, gamma, noise_std,
                              backend=backend, hw_aware=hw_aware)
    if key is None:
        key = jax.random.PRNGKey(0)
    return fit(loss_fn, params, optimizer, num_steps, key, log_every,
               scan_chunk=scan_chunk)


# ---------------------------------------------------------------------------
# Derivative-matching warm start (collocation pretraining)
# ---------------------------------------------------------------------------

def finite_difference_derivatives(ts: jax.Array, ys: jax.Array):
    """Central differences on the interior points: (T-2,) ts, ys, dys."""
    dt = ts[2:] - ts[:-2]
    dys = (ys[2:] - ys[:-2]) / dt[:, None]
    return ts[1:-1], ys[1:-1], dys


def derivative_matching_loss(field, ts_mid, ys_mid, dys):
    def loss_fn(params, key):
        del key
        preds = jax.vmap(lambda t, y: field(t, y, params))(ts_mid, ys_mid)
        return jnp.mean(jnp.abs(preds - dys))
    return loss_fn


def pretrain_derivatives(field, params, ts, ys, *, optimizer,
                         num_steps: int, log_every: int = 0):
    ts_mid, ys_mid, dys = finite_difference_derivatives(ts, ys)
    loss_fn = derivative_matching_loss(field, ts_mid, ys_mid, dys)
    return fit(loss_fn, params, optimizer, num_steps, log_every=log_every)


# ---------------------------------------------------------------------------
# Baseline training (teacher-forced recurrent forecasters / ResNet)
# ---------------------------------------------------------------------------

def train_forecaster(model, params, ys: jax.Array, *, optimizer,
                     num_steps: int, noise_std: float = 0.0,
                     key: jax.Array | None = None, log_every: int = 0):
    def loss_fn(params, key):
        inp = ys
        if noise_std > 0 and key is not None:
            inp = ys + noise_std * jax.random.normal(key, ys.shape)
        preds = model.teacher_forced(params, inp)
        return l1(preds, ys[1:])
    if key is None:
        key = jax.random.PRNGKey(0)
    return fit(loss_fn, params, optimizer, num_steps, key, log_every)


def train_recurrent_resnet(model, params, us: jax.Array, ys: jax.Array, *,
                           optimizer, num_steps: int,
                           segment_len: int = 50,
                           key: jax.Array | None = None, log_every: int = 0):
    """Teacher-forced segment training of h_{t+1} = h_t + f([u_t, h_t])."""
    T = ys.shape[0]
    L = segment_len
    S = (T - 1) // L
    idx = jnp.arange(S)[:, None] * L + jnp.arange(L + 1)[None, :]
    ys_seg = ys[idx]                      # (S, L+1, D)
    us_seg = us[idx[:, :-1]]              # (S, L, U)

    def loss_fn(params, key):
        del key
        preds = jax.vmap(lambda y0, u: model.rollout(params, y0, u))(
            ys_seg[:, 0], us_seg)
        return l1(preds, ys_seg)

    return fit(loss_fn, params, optimizer, num_steps, key, log_every)
