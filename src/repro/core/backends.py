"""Pluggable execution backends: one trained twin, many substrates.

The paper's central claim is substrate portability — the same trained
neural-ODE weights execute digitally (GPU/TPU), on analogue memristor
crossbars, or (our TPU transposition) inside the weights-stationary fused
Pallas kernel.  This module is the single abstraction behind all three:

    Backend.program(field, params) -> ExecState     ("deploy" the weights)
    Backend.apply(state, t, x)     -> dx/dt         (one vector-field eval)
    Backend.rollout(state, y0, ts) -> ys            (full IVP solve)
    Backend.rollout_batch(state, y0s, ts) -> yss    (fleet of N twins)

``program`` is the deployment step: for the digital backend it is the
identity, for the analogue backend it writes conductances onto simulated
crossbars (quantisation + programming noise, frozen), and for the fused
backend it stages float32 weight/bias operands for VMEM residency.

``rollout`` has a default odeint-based implementation (direct RK4 over
``apply``); backends override it when the substrate integrates
differently — the fused backend runs the whole RK4 trajectory inside one
``pallas_call``, sampling the drive at half-steps itself.

``rollout_batch`` is the fleet primitive: N independent initial
conditions (and optionally per-twin drive parameters) in ONE device
program — vmap for digital/analogue, grid batch-tiling for fused Pallas.
It is also the mesh-aware entry point: passing ``mesh=`` (a
``jax.sharding.Mesh`` with a ``"twins"`` axis) shards the fleet dimension
across devices with ``shard_map`` — weights replicated, ``y0s`` and
per-twin drive parameters split, each device running its slice through
the SAME per-device implementation (``rollout_batch_local``).  Backends
therefore customise ``rollout_batch_local`` and inherit multi-device
serving for free; the sharding machinery itself lives in
:mod:`repro.launch.fleet_serving`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adjoint import odeint_adjoint
from repro.core.analogue import (AnalogueMLPVectorField, AnalogueSpec,
                                 VerifyConfig, program_mlp,
                                 program_mlp_with_verify, stage_uint8)
from repro.core.faults import FaultModel, apply_faults_to_mlp
from repro.core.ode import make_odeint, odeint
from repro.kernels.fused_ode_mlp import (DEFAULT_VMEM_BUDGET, MLPField,
                                         rollout_plan)

Pytree = Any


class ExecState(NamedTuple):
    """A programmed twin: the executable field plus whatever parameters
    still live off-substrate (None when the weights are frozen in)."""
    field: Callable          # f(t, y, params) -> dy/dt
    params: Pytree           # pytree threaded to the field, or None
    extra: Any = None        # backend-private staging (e.g. fused operands)


def kernel_field(field: Callable):
    """The fused kernel's form of a vector field (``field.kernel_field()``:
    :class:`~repro.kernels.fused_ode_mlp.MLPField` or ``CDEField``);
    ``None`` for a field that names none, which the kernels take as the
    ReLU MLP of its parameters."""
    make = getattr(field, "kernel_field", None)
    return None if make is None else make()


def _require_mlp(field: Callable, backend: str) -> None:
    """The crossbar backends program ReLU-MLP layers; refuse another field
    instead of serving the wrong one."""
    kf = kernel_field(field)
    if kf is not None and not isinstance(kf, MLPField):
        raise ValueError(f"{backend} programs ReLU-MLP fields onto crossbars,"
                         f" not {type(field).__name__}")


def _with_drive(state: ExecState, drive: Optional[Callable]) -> ExecState:
    """Re-bind the drive u(t) on a programmed field (fields are frozen
    dataclasses with a ``drive`` attribute)."""
    return state._replace(field=dataclasses.replace(state.field, drive=drive))


@runtime_checkable
class Backend(Protocol):
    """Structural type every execution substrate implements.

    Lifecycle: ``program`` once per set of weights, then any number of
    ``apply``/``rollout``/``rollout_batch`` calls against the returned
    :class:`ExecState`.  See ``docs/architecture.md`` for how the layers
    compose and :class:`BaseBackend` for the default implementations.
    """

    name: str

    def program(self, field: Callable, params: Pytree) -> ExecState:
        """Deploy ``params`` onto the substrate; returns the programmed
        state (digital: identity; analogue: conductances written, frozen;
        fused: f32 operands staged for VMEM residency)."""
        ...

    def apply(self, state: ExecState, t: jax.Array, x: jax.Array) -> jax.Array:
        """One vector-field evaluation dx/dt = f(t, x) on the substrate."""
        ...

    def rollout(self, state: ExecState, y0: jax.Array, ts: jax.Array, *,
                method: str = "rk4", steps_per_interval: int = 1,
                gradient: str = "direct") -> jax.Array:
        """Solve the IVP from ``y0`` over ``ts`` -> (T+1, D) trajectory."""
        ...

    def rollout_batch(self, state: ExecState, y0s: jax.Array,
                      ts: jax.Array, **kw) -> jax.Array:
        """Fleet solve: N initial conditions -> (N, T+1, D) in one device
        program; ``mesh=`` shards the fleet axis across devices."""
        ...


@dataclasses.dataclass(frozen=True, eq=False)
class BaseBackend:
    """Default implementations shared by the concrete backends."""

    name = "base"

    def program(self, field: Callable, params: Pytree) -> ExecState:
        return ExecState(field=field, params=params)

    def apply(self, state: ExecState, t, x):
        return state.field(t, x, state.params)

    def rollout(self, state: ExecState, y0, ts, *, method: str = "rk4",
                steps_per_interval: int = 1,
                gradient: str = "direct") -> jax.Array:
        """Default: direct fixed-step odeint over ``apply``."""
        del gradient  # substrate-specific backends decide differentiability
        if method == "dopri5":
            return make_odeint("dopri5")(state.field, y0, ts, state.params)
        return odeint(state.field, y0, ts, state.params, method=method,
                      steps_per_interval=steps_per_interval)

    def rollout_batch(self, state: ExecState, y0s, ts, *,
                      drive_family: Optional[Callable] = None,
                      drive_params: Optional[jax.Array] = None,
                      mesh=None, **kw) -> jax.Array:
        """Fleet rollout: N independent twins in one device program.

        ``drive_family(t, theta)`` + per-twin ``drive_params`` (N, ...)
        re-binds each fleet member's drive; returns (N, T+1, D) matching
        ``jnp.stack([rollout(y0_i) for i])``.

        ``mesh``: optional ``jax.sharding.Mesh`` with a ``"twins"`` axis.
        When given, the fleet dimension is sharded across the mesh with
        ``shard_map`` (weights replicated, N padded up to a multiple of
        the shard count, padded rows dropped from the result) and each
        device runs
        :meth:`rollout_batch_local` on its slice; ``mesh=None`` runs the
        whole fleet on the current device.  Results are identical either
        way — sharding only changes placement.
        """
        if mesh is not None:
            from repro.launch.fleet_serving import shard_rollout_batch
            return shard_rollout_batch(self, state, y0s, ts, mesh=mesh,
                                       drive_family=drive_family,
                                       drive_params=drive_params, **kw)
        return self.rollout_batch_local(state, y0s, ts,
                                        drive_family=drive_family,
                                        drive_params=drive_params, **kw)

    def rollout_batch_local(self, state: ExecState, y0s, ts, *,
                            drive_family: Optional[Callable] = None,
                            drive_params: Optional[jax.Array] = None,
                            **kw) -> jax.Array:
        """Single-device fleet implementation (the shard body): vmap N
        independent rollouts into one device program.  Subclasses override
        THIS (not ``rollout_batch``) to keep the mesh dispatch in one
        place."""
        if drive_family is None:
            return jax.vmap(lambda y0: self.rollout(state, y0, ts, **kw))(y0s)

        def single(y0, theta):
            st = _with_drive(state, lambda t: drive_family(t, theta))
            return self.rollout(st, y0, ts, **kw)

        return jax.vmap(single)(y0s, drive_params)

    # -- resume-from-state rollouts (streaming serving) ---------------------
    @staticmethod
    def _resume_starts(start_steps, n: int) -> np.ndarray:
        """Normalise ``start_steps`` to a concrete (N,) int64 vector of
        per-twin global step offsets.  Offsets are HOST values by design
        — they index the canonical time grid, which must be computed in
        float64 outside any trace (see :func:`repro.kernels.ops
        .window_times`); a traced offset would force the 1-ulp-wrong
        on-device grid arithmetic the contract exists to forbid."""
        if start_steps is None:
            return np.zeros(n, np.int64)
        if isinstance(start_steps, jax.core.Tracer):
            raise ValueError(
                "rollout_batch_resumed: start_steps must be concrete host "
                "integers (they parameterise the canonical float64 time "
                "grid); do not pass them through jit")
        starts = np.asarray(start_steps, np.int64)
        if starts.ndim == 0:
            starts = np.broadcast_to(starts, (n,)).copy()
        if starts.shape != (n,) or (starts < 0).any():
            raise ValueError(
                f"rollout_batch_resumed: start_steps must be {n} "
                f"non-negative per-twin step offsets, got shape "
                f"{starts.shape}")
        return starts

    def rollout_batch_resumed(self, state: ExecState, ys, *, dt: float,
                              num_steps: int, t0: float = 0.0,
                              start_steps=None,
                              drive_family: Optional[Callable] = None,
                              drive_params: Optional[jax.Array] = None,
                              **kw) -> jax.Array:
        """Fleet rollout resuming each twin from a carried (y, t) instead
        of t0: twin i advances ``num_steps`` RK4 steps from its own
        global step ``start_steps[i]`` on the canonical uniform grid
        ``t = t0 + dt*k``.  Returns (N, num_steps+1, D) with row 0 the
        carried states.

        The determinism contract (``docs/serving.md``, enforced by
        ``tests/test_streaming.py``): every time value is derived in
        float64 from ``(t0, dt, global step index)`` and rounded to f32
        once (:func:`repro.kernels.ops.window_times`), so serving
        ``[0, k)`` then ``[k, T)`` through a state store is bit-identical
        (f32 substrates) to serving ``[0, T)`` in one call — splitting
        never changes the arithmetic, only where the HBM round-trip
        happens.  ``start_steps=None`` means all twins start at t0
        (fresh rollout through the same code path).
        """
        from repro.kernels.ops import window_times
        ys = jnp.asarray(ys)
        starts = self._resume_starts(start_steps, ys.shape[0])
        tss = window_times(t0, dt, int(num_steps), starts)     # (N, H+1)
        if drive_family is None:
            return jax.vmap(
                lambda y, ts: self.rollout(state, y, ts, **kw))(ys, tss)

        def single(y, ts, theta):
            st = _with_drive(state, lambda t: drive_family(t, theta))
            return self.rollout(st, y, ts, **kw)

        return jax.vmap(single)(ys, tss, drive_params)


# ---------------------------------------------------------------------------
# Digital backend — the training substrate
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class DigitalBackend(BaseBackend):
    """Plain jnp execution (the current training path, bit-for-bit).

    The only backend that is differentiable through the solve: supports
    the adjoint method (O(1) memory) and backprop-through-solver, plus the
    adaptive dopri5 integrator.
    """

    name = "digital"

    def rollout(self, state: ExecState, y0, ts, *, method: str = "rk4",
                steps_per_interval: int = 1,
                gradient: str = "adjoint") -> jax.Array:
        if method == "dopri5":
            return make_odeint("dopri5")(state.field, y0, ts, state.params)
        if gradient == "adjoint":
            return odeint_adjoint(state.field, y0, ts, state.params,
                                  method, steps_per_interval)
        return odeint(state.field, y0, ts, state.params, method=method,
                      steps_per_interval=steps_per_interval)


# ---------------------------------------------------------------------------
# Analogue backend — simulated memristor crossbars
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class AnalogueBackend(BaseBackend):
    """Deploys the MLP onto simulated differential crossbar pairs.

    ``program`` performs the paper's deployment: differential conductance
    mapping, 6-bit quantisation and multiplicative programming noise,
    frozen at program time; ``apply``/``rollout`` then re-sample read
    noise per VMM.  Weights no longer exist as parameters afterwards
    (``ExecState.params is None``) — they live in the crossbars.

    ``progs`` short-circuits programming with already-written crossbars
    (the ``deploy_analogue`` legacy shim uses this).

    ``storage="uint8"`` additionally stages each array's 6-bit level
    indices (requires ``prog_noise=0`` — noise moves conductances off
    the level grid): large noise-free reads then execute on the blocked
    Pallas kernel with dequant fused into the MXU feed instead of
    reading float conductances (see ``analogue_matmul``'s dispatch).

    Robustness knobs (see :mod:`repro.core.faults` and
    ``docs/robustness.md``): ``faults`` degrades the array with the
    composed device-fault model — stuck cells pinned, single-pulse write
    failures, and a conductance-drift snapshot after ``n_reads``
    evaluations; ``verify`` switches programming to the closed-loop
    write–verify routine (:func:`repro.core.analogue.program_with_verify`
    — read-back, bounded retry with backoff, differential-pair remap of
    stuck cells).  Either one makes ``program`` simulate the write
    physics pulse-by-pulse and surface the per-layer
    :class:`repro.core.analogue.RepairReport` list through
    ``ExecState.extra["repair_reports"]``.
    """

    name = "analogue"
    spec: AnalogueSpec = AnalogueSpec()
    prog_key: Optional[jax.Array] = None
    read_key: Optional[jax.Array] = None
    progs: Optional[tuple] = None
    storage: str = "float"          # "float" | "uint8" level indices
    faults: Optional[FaultModel] = None
    verify: Optional[VerifyConfig] = None
    n_reads: int = 0                # drift snapshot: reads already served

    def program(self, field: Callable, params: Pytree) -> ExecState:
        _require_mlp(field, "AnalogueBackend")
        if self.storage not in ("float", "uint8"):
            raise ValueError(
                f"AnalogueBackend storage={self.storage!r}; have "
                f"'float', 'uint8'")
        if (self.storage == "uint8" and self.faults is not None
                and self.faults.drift is not None):
            raise ValueError(
                "AnalogueBackend: conductance drift moves cells off the "
                "6-bit level grid, so storage='uint8' cannot carry a "
                "drift snapshot — use float storage, or "
                "FusedAnalogueBackend whose kernel drifts in-kernel")
        progs, reports = self.progs, None
        if progs is None:
            if params is None:
                raise ValueError(
                    "AnalogueBackend needs params to program the crossbars "
                    "(or pre-programmed `progs`)")
            key = (self.prog_key if self.prog_key is not None
                   else jax.random.PRNGKey(0))
            if self.faults is not None or self.verify is not None:
                # One code path simulates the write physics: 'naive'
                # faulty programming is the same routine with zero
                # retries (a single uncorrected pulse train).
                vc = (self.verify if self.verify is not None
                      else VerifyConfig(max_retries=0))
                progs, reports = program_mlp_with_verify(
                    key, params, self.spec, faults=self.faults, verify=vc)
                progs = tuple(progs)
                if self.faults is not None and self.faults.drift is not None:
                    drift_only = dataclasses.replace(
                        self.faults, stuck=None, write_fail=None)
                    progs = tuple(apply_faults_to_mlp(
                        progs, drift_only, self.spec, n_reads=self.n_reads))
            else:
                progs = tuple(program_mlp(key, params, self.spec))
        if self.storage == "uint8":
            progs = tuple(stage_uint8(p, self.spec) for p in progs)
        a_field = AnalogueMLPVectorField(
            progs=progs, spec=self.spec,
            drive=getattr(field, "drive", None), key=self.read_key)
        extra = None if reports is None else {"repair_reports": reports}
        return ExecState(field=a_field, params=None, extra=extra)


# ---------------------------------------------------------------------------
# Fused-Pallas backend — weights-stationary TPU kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class FusedPallasBackend(BaseBackend):
    """Whole-trajectory RK4 inside one ``pallas_call`` (weights pinned in
    VMEM — the TPU transposition of in-memory computing).

    ``rollout`` ignores the per-step odeint and instead samples the drive
    on the RK4 half-step grid and hands the full solve to
    :func:`repro.kernels.fused_ode_mlp.fused_node_rollout`.  Requires a
    uniform, concrete time grid and ``method='rk4'``.

    The substrate is DIFFERENTIABLE: any ``gradient`` mode other than
    ``"stopgrad"`` routes the solve through the reverse-time
    checkpoint/replay kernel (:mod:`repro.kernels.fused_ode_mlp_bwd`),
    so the same weights-stationary program that serves the fleet also
    trains it (discretise-then-optimise — gradients match
    backprop-through-the-unrolled-RK4 to float32 rounding).  Pass
    ``gradient="stopgrad"`` to detach an inference-only solve.

    ``rollout_batch`` tiles the fleet across the Pallas grid — one cell
    per ``batch_tile`` twins, weights broadcast to every cell — instead
    of vmapping N separate solves.  Fleet sizes that do not divide the
    tile are padded up to the next tile multiple (padded rows replicate
    the last twin and are dropped from the result), so a prime fleet
    size costs one extra tile instead of degenerating to 1-twin cells.

    Long horizons stream through VMEM in time chunks: the kernel carries
    the integration state across a second grid dimension, so ``T`` is
    unbounded (serving at T>=10k works) while the weights stay resident.
    ``time_chunk=None`` auto-sizes the chunk from ``vmem_budget_bytes``.

    ``precision`` selects the mixed-precision policy of the substrate
    ("f32" | "bf16" | "bf16_f32acc"; ``None`` = auto — bf16_f32acc on
    TPU, f32 elsewhere): the bf16 policies store weights, drive and
    trajectory slabs at half width (the VMEM planner packs ~2x the time
    chunk) while matmuls accumulate at f32 and gradients always come
    back f32.  Error model: ``docs/kernels.md``.  Every ``rollout`` /
    ``rollout_batch`` call accepts a per-call ``precision=`` override.
    """

    name = "fused_pallas"
    batch_tile: int = 64
    time_chunk: Optional[int] = None        # None = auto from VMEM budget
    interpret: Optional[bool] = None        # None = auto (TPU -> compiled)
    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET
    precision: Optional[str] = None         # None = auto (TPU -> bf16_f32acc)

    # -- staging -----------------------------------------------------------
    def program(self, field: Callable, params: Pytree) -> ExecState:
        """Stage full-precision (f32) master operands; the precision
        policy rounds them to its storage dtype at solve time.  Staging
        the masters — not pre-rounded bf16 copies — keeps the per-call
        ``precision`` override honest: ``precision="f32"`` on a
        bf16-policy backend really is the exact path, and bf16→f32→bf16
        round-trips cannot double-round.  ``extra["field"]`` is the
        field's kernel form (:func:`kernel_field`), which the solve hands
        the kernel: the ReLU MLP, or the neural CDE whose output layer the
        kernel restages."""
        if params is None:
            raise ValueError("FusedPallasBackend needs the field's params")
        weights = [p["w"].astype(jnp.float32) for p in params]
        biases = [p["b"].astype(jnp.float32) for p in params]
        return ExecState(field=field, params=params,
                         extra={"weights": weights, "biases": biases,
                                "field": kernel_field(field)})

    def _grid(self, ts: jax.Array, steps_per_interval: int):
        """Validate + densify the time grid; returns (ts_fine, dt, sub)."""
        try:
            tsn = np.asarray(ts, dtype=np.float64)
        except jax.errors.TracerArrayConversionError as e:
            raise ValueError(
                "FusedPallasBackend needs a concrete (non-traced) time "
                "grid: the step count and dt are kernel compile-time "
                "constants. Close over ts instead of passing it as a jit "
                "argument.") from e
        if tsn.size < 2:
            raise ValueError("FusedPallasBackend needs a uniform time grid")
        # Uniformity is judged on the grid VALUES, not consecutive diffs:
        # float32 linspace diffs wobble by ~eps*t_max (which falsely
        # rejected T>=10k grids under a fixed rtol), but the values stay
        # within float32 rounding of the ideal line — and that distance
        # is exactly the time error incurred by integrating with a
        # constant dt.
        dt0 = (tsn[-1] - tsn[0]) / (tsn.size - 1)
        drift = np.abs(tsn - (tsn[0] + dt0 * np.arange(tsn.size))).max()
        tol = max(32 * np.finfo(np.float32).eps * np.abs(tsn).max(), 1e-9)
        if dt0 == 0 or drift > tol:
            raise ValueError("FusedPallasBackend needs a uniform time grid")
        sub = int(steps_per_interval)
        T = (tsn.size - 1) * sub
        ts_fine = jnp.asarray(
            np.linspace(tsn[0], tsn[-1], T + 1), dtype=jnp.float32)
        dt = float(dt0) / sub
        return ts_fine, dt, sub

    def _u_half(self, drive: Optional[Callable], ts_fine: jax.Array):
        """Sample u(t) on the RK4 half-step grid, (2T+1, Du)."""
        from repro.kernels.ops import half_step_drive
        T = ts_fine.shape[0] - 1
        if drive is None:
            return jnp.zeros((2 * T + 1, 0), jnp.float32)
        return half_step_drive(drive, ts_fine).astype(jnp.float32)

    def _solve(self, state: ExecState, y0s, uh, dt, bt, gradient,
               precision=None, step_offset=0):
        """Dispatch the fused solve in the requested gradient mode.

        Every differentiable mode ('adjoint'/'direct'/'fused_vjp') maps
        onto the one substrate-native VJP (reverse-time checkpoint/
        replay); 'stopgrad' detaches.  The dispatch itself lives in
        :func:`repro.kernels.ops.fused_node_rollout` — one copy.
        ``precision=None`` falls back to the backend's policy.
        ``step_offset`` (the global step index of ``y0s`` in a resumed
        rollout) is irrelevant here — the digital RK4 arithmetic is
        time-translation-invariant once the drive is sampled — but the
        analogue subclass keys its noise/drift streams on it.

        NOTE: under the fused VJP the drive is data (zero cotangent), so
        gradients w.r.t. per-twin ``drive_params`` are silently zero on
        this substrate — calibrate drive parameters on the digital
        backend.
        """
        del step_offset
        from repro.kernels import ops
        params = [{"w": w, "b": b} for w, b in
                  zip(state.extra["weights"], state.extra["biases"])]
        mode = "stopgrad" if gradient == "stopgrad" else "fused_vjp"
        return ops.fused_node_rollout(
            params, y0s, uh, dt, batch_tile=bt, time_chunk=self.time_chunk,
            interpret=self.interpret,
            vmem_budget_bytes=self.vmem_budget_bytes, gradient=mode,
            precision=self.precision if precision is None else precision,
            field=state.extra.get("field"))

    def time_chunks(self, state: ExecState, ys, uh) -> int:
        """Time chunks of the detached (serving) solve of the carried
        states ``ys`` (B, D) over the drive window ``uh``: the forward
        kernel's own plan (``fused_ode_mlp.rollout_plan``), from shapes
        alone."""
        return rollout_plan(
            *ys.shape, uh.shape, state.extra["weights"],
            state.extra["biases"], batch_tile=self.batch_tile,
            time_chunk=self.time_chunk,
            vmem_budget_bytes=self.vmem_budget_bytes,
            precision=self.precision, field=state.extra["field"]).num_chunks

    def _u_half_window(self, state: ExecState, t0, dt, num_steps,
                       starts: np.ndarray,
                       drive_family: Optional[Callable],
                       drive_params: Optional[jax.Array]) -> jax.Array:
        """Drive on the canonical half-step window of each twin: shared
        (2H+1, Du) when every twin sits at the same global step with one
        drive, per-twin (N, 2H+1, Du) otherwise (ragged phases — the
        kernel's per-tile drive slabs take it from there)."""
        from repro.kernels import ops
        drive = getattr(state.field, "drive", None)
        if drive_family is not None:
            ths = ops.half_step_times(t0, dt, num_steps, starts)

            def row(ts_row, theta):
                u = jax.vmap(lambda t: drive_family(t, theta))(ts_row)
                return u[:, None] if u.ndim == 1 else u

            return jax.vmap(row)(ths, drive_params).astype(jnp.float32)
        if drive is None:
            return jnp.zeros((2 * num_steps + 1, 0), jnp.float32)
        homogeneous = starts.size > 0 and bool((starts == starts[0]).all())
        start = int(starts[0]) if homogeneous else starts
        return ops.sample_drive_window(
            drive, t0, dt, num_steps, start).astype(jnp.float32)

    def rollout_batch_resumed(self, state: ExecState, ys, *, dt: float,
                              num_steps: int, t0: float = 0.0,
                              start_steps=None,
                              drive_family: Optional[Callable] = None,
                              drive_params: Optional[jax.Array] = None,
                              method: str = "rk4",
                              steps_per_interval: int = 1,
                              gradient: str = "fused_vjp",
                              precision: Optional[str] = None) -> jax.Array:
        """Resume-from-state fleet solve on the fused substrate.

        Each twin's carried state enters the kernel through the same
        storage-dtype seed path as trajectory rows leave it (see the
        chunk-carry contract in :mod:`repro.kernels.fused_ode_mlp`), and
        its drive window is sampled on the canonical global half-step
        grid — so splitting a rollout at any step and resuming from the
        stored row is bit-identical to the uninterrupted solve under f32
        (and pure bf16) storage, and within one storage rounding under
        bf16_f32acc.  Mixed phases batch fine: heterogeneous
        ``start_steps`` switch to per-twin drive slabs.
        """
        from repro.kernels.fused_ode_mlp import pad_fleet_to_tile
        if method != "rk4" or steps_per_interval != 1:
            raise ValueError(
                "FusedPallasBackend.rollout_batch_resumed integrates "
                "plain RK4 on the canonical step grid (method='rk4', "
                f"steps_per_interval=1), got method={method!r}, "
                f"steps_per_interval={steps_per_interval}")
        ys = jnp.asarray(ys)
        starts = self._resume_starts(start_steps, ys.shape[0])
        uh = self._u_half_window(state, t0, dt, int(num_steps), starts,
                                 drive_family, drive_params)
        homogeneous = starts.size > 0 and bool((starts == starts[0]).all())
        offset = int(starts[0]) if homogeneous else 0
        y0s, uh, bt, B = pad_fleet_to_tile(ys, uh, self.batch_tile)
        traj = self._solve(state, y0s, uh, float(dt), bt, gradient,
                           precision, step_offset=offset)
        return jnp.transpose(traj[:, :B], (1, 0, 2))

    # -- execution ---------------------------------------------------------
    def rollout(self, state: ExecState, y0, ts, *, method: str = "rk4",
                steps_per_interval: int = 1,
                gradient: str = "fused_vjp",
                precision: Optional[str] = None) -> jax.Array:
        if method != "rk4":
            raise ValueError(
                f"FusedPallasBackend integrates RK4 only, got {method!r}")
        ts_fine, dt, sub = self._grid(ts, steps_per_interval)
        uh = self._u_half(getattr(state.field, "drive", None), ts_fine)
        traj = self._solve(state, y0[None, :], uh, dt, 1, gradient,
                           precision)
        return traj[::sub, 0, :]

    def rollout_batch_local(self, state: ExecState, y0s, ts, *,
                            drive_family: Optional[Callable] = None,
                            drive_params: Optional[jax.Array] = None,
                            method: str = "rk4", steps_per_interval: int = 1,
                            gradient: str = "fused_vjp",
                            precision: Optional[str] = None) -> jax.Array:
        """Per-device fleet solve: tile the local batch across the Pallas
        grid (weights broadcast to every cell, per-twin drives sampled on
        the half-step grid per tile).  ``precision`` overrides the
        backend's mixed-precision policy per call (it rides through
        ``rollout_batch(mesh=...)``'s ``solver_kw``, so sharded fleets
        serve reduced precision too)."""
        if method != "rk4":
            raise ValueError(
                f"FusedPallasBackend integrates RK4 only, got {method!r}")
        ts_fine, dt, sub = self._grid(ts, steps_per_interval)
        B = y0s.shape[0]
        if drive_family is None:
            uh = self._u_half(getattr(state.field, "drive", None), ts_fine)
        else:
            # per-twin drive: (B, 2T+1, Du) -> per-tile blocks in-kernel
            uh = jax.vmap(
                lambda th_: self._u_half(lambda t: drive_family(t, th_),
                                         ts_fine))(drive_params)
        # pad the fleet up to a tile multiple instead of shrinking the
        # tile to a divisor: a prime B used to degenerate to bt=1 and one
        # grid cell per twin (B=1021 -> 1021 cells); now it costs at most
        # one padded tile.
        from repro.kernels.fused_ode_mlp import pad_fleet_to_tile
        y0s, uh, bt, B = pad_fleet_to_tile(y0s, uh, self.batch_tile)
        traj = self._solve(state, y0s, uh, dt, bt, gradient, precision)
        return jnp.transpose(traj[::sub, :B], (1, 0, 2))


# ---------------------------------------------------------------------------
# Fused-analogue backend — crossbar semantics on the weights-stationary kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class FusedAnalogueBackend(FusedPallasBackend):
    """The analogue substrate on the fused kernel: one ``pallas_call``
    runs the whole RK4 trajectory with the *crossbar* read semantics
    traced in-kernel (:mod:`repro.kernels.fused_analogue`) — the jnp
    simulator's per-step dispatch is gone, while ``program`` stays the
    paper's deployment exactly (same ``program_mlp``, bitwise-identical
    conductances to :class:`AnalogueBackend`).

    ``program`` is the one-time deployment step — run it once per set of
    weights (outside any per-request jit) so the frozen conductances are
    concrete, like a physical array would be; serving then closes over
    them.  ``storage="uint8"`` deploys the 6-bit level indices instead
    of float conductances (4x less stationary weight traffic, dequant
    fused into the MXU feed; requires ``prog_noise=0``).

    Read noise (``spec.read_noise``) is re-sampled per crossbar
    evaluation from a counter-derived stream keyed on ``read_seed`` —
    deterministic and replayable, but a *different* sequence from the
    ``jax.random`` stream of :class:`AnalogueBackend` (equal in
    distribution, not bitwise).

    Serving is inference-only: frozen conductances are physical
    quantities, so the fused analogue rollout detaches every gradient
    mode and always runs float32 (the mixed-precision policies do not
    apply).  ``trainable=True`` arms the *differentiable training mode*:
    ``program`` additionally stages the float32 master weights, and a
    non-detached ``_solve`` passes them through the hardware-aware write
    path (:mod:`repro.train.hw_aware` — STE quantise + programming/read
    noise + this backend's fault model, keyed by ``read_seed`` and
    ``step_offset``) before integrating on the fused digital kernel with
    its reverse-time VJP.  Forward sees device-degraded weights; the
    gradient reaches the masters through the straight-through estimator.
    (`train_twin(backend="analogue_fused")` routes through the same
    transform at the loss level — see ``segment_loss_fn``.)

    ``apply`` (single vector-field evaluations) keeps the jnp simulator
    path of the programmed field — only the rollouts are fused.
    """

    name = "analogue_fused"
    spec: AnalogueSpec = AnalogueSpec()
    prog_key: Optional[jax.Array] = None
    read_seed: int = 0
    storage: str = "float"          # "float" | "uint8" level indices
    faults: Optional[FaultModel] = None
    verify: Optional[VerifyConfig] = None
    n_reads: int = 0                # reads already served before t0 (drift)
    trainable: bool = False         # arm the differentiable training mode

    # -- deployment --------------------------------------------------------
    def program(self, field: Callable, params: Pytree) -> ExecState:
        _require_mlp(field, "FusedAnalogueBackend")
        if self.storage not in ("float", "uint8"):
            raise ValueError(
                f"FusedAnalogueBackend storage={self.storage!r}; have "
                f"'float', 'uint8'")
        if params is None:
            raise ValueError(
                "FusedAnalogueBackend needs params to program the "
                "crossbars")
        key = (self.prog_key if self.prog_key is not None
               else jax.random.PRNGKey(0))
        reports = None
        if self.faults is not None or self.verify is not None:
            # Same write-physics simulation as AnalogueBackend: stuck
            # cells and failed pulses are baked into the deployed
            # conductances (that IS the physical array); the kernel then
            # re-derives the same stuck masks in-kernel (idempotent) and
            # advances the drift decay live with the step count.
            vc = (self.verify if self.verify is not None
                  else VerifyConfig(max_retries=0))
            progs, reports = program_mlp_with_verify(
                key, params, self.spec, faults=self.faults, verify=vc)
            progs = tuple(progs)
        else:
            progs = tuple(program_mlp(key, params, self.spec))
        staged = {
            "scales": jnp.stack([p["scale"] for p in progs]),
            "g_step": None,
            "g_min": self.spec.g_min,
            "g_max": self.spec.g_max,
            "v_clamp": self.spec.v_clamp,
        }
        if self.faults is not None:
            staged["fault"] = self.faults.kernel_args(self.n_reads)
        if reports is not None:
            staged["repair_reports"] = reports
        if self.storage == "uint8":
            progs = tuple(stage_uint8(p, self.spec) for p in progs)
            staged["gps"] = [p["gp_idx"] for p in progs]
            staged["gms"] = [p["gm_idx"] for p in progs]
            staged["g_step"] = ((self.spec.g_max - self.spec.g_min)
                                / (self.spec.levels - 1))
        else:
            staged["gps"] = [p["gp"].astype(jnp.float32) for p in progs]
            staged["gms"] = [p["gm"].astype(jnp.float32) for p in progs]
        a_field = AnalogueMLPVectorField(
            progs=progs, spec=self.spec,
            drive=getattr(field, "drive", None), key=None)
        if self.trainable:
            # training mode keeps the f32 masters alongside the frozen
            # conductances — the differentiable _solve path reads them
            staged["weights"] = [p["w"].astype(jnp.float32)
                                 for p in params]
            staged["biases"] = [p["b"].astype(jnp.float32)
                                for p in params]
        return ExecState(field=a_field, params=None, extra=staged)

    # -- execution ---------------------------------------------------------
    def _solve(self, state: ExecState, y0s, uh, dt, bt, gradient,
               precision=None, step_offset=0):
        """Dispatch the fused analogue solve.  ``precision`` is ignored
        (the substrate is float32).  ``step_offset`` keys the read-noise
        salts and drift exponent to the global step index of ``y0s``, so
        a resumed rollout replays the uninterrupted noise stream — it is
        only exact when the whole batch shares one offset
        (``rollout_batch_resumed`` passes 0 for mixed-phase batches:
        deterministic per batch, equal in distribution, not a bitwise
        replay).

        Serving (``trainable=False``) always detaches, whatever
        ``gradient`` says.  With ``trainable=True`` and a non-detached
        ``gradient``, the solve becomes differentiable: the staged f32
        masters go through the hardware-aware write path (one device
        realisation keyed by ``(read_seed, step_offset)``) and the fused
        digital kernel's reverse-time VJP carries the gradient back to
        them through the STE."""
        del precision
        from repro.kernels import ops
        if self.trainable and gradient != "stopgrad":
            from repro.train.hw_aware import (HwAwareConfig,
                                              hw_aware_params)
            masters = [{"w": w, "b": b}
                       for w, b in zip(state.extra["weights"],
                                       state.extra["biases"])]
            cfg = HwAwareConfig.from_backend(self, k_draws=1)
            eff = hw_aware_params(masters, cfg, step_offset, draw=0)
            return ops.fused_node_rollout(
                eff, y0s, uh, dt, batch_tile=bt,
                time_chunk=self.time_chunk, interpret=self.interpret,
                vmem_budget_bytes=self.vmem_budget_bytes,
                gradient="fused_vjp", precision="f32")
        del gradient
        return ops.fused_analogue_rollout(
            state.extra, y0s, uh, dt, batch_tile=bt,
            time_chunk=self.time_chunk, interpret=self.interpret,
            vmem_budget_bytes=self.vmem_budget_bytes,
            read_noise=self.spec.read_noise, noise_seed=self.read_seed,
            step_offset=step_offset)

    def time_chunks(self, state: ExecState, ys, uh) -> int:
        """Not counted: the analogue kernel plans its own conductance
        operands, and ``StreamStats.time_chunks`` counts the digital
        fused kernel's."""
        return 0


DEFAULT_BACKEND = DigitalBackend()

#: Registry of substrate names accepted anywhere a Backend is expected
#: (``twin.with_backend("fused_pallas")``, recipe ``backend=`` kwargs).
BACKENDS = {
    "digital": DigitalBackend,
    "analogue": AnalogueBackend,
    "fused_pallas": FusedPallasBackend,
    "analogue_fused": FusedAnalogueBackend,
}


def resolve_backend(backend) -> Backend:
    """Accept a Backend instance, a registry name, or None (digital)."""
    if backend is None:
        return DEFAULT_BACKEND
    if isinstance(backend, str):
        try:
            return BACKENDS[backend]()
        except KeyError:
            raise ValueError(
                f"unknown backend {backend!r}; have {sorted(BACKENDS)}")
    return backend
