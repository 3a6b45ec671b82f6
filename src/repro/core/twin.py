"""Digital-twin façade: driven and autonomous continuous-time twins.

A twin = (vector field, integrator, gradient mode) + a pluggable
execution backend (digital jnp / analogue crossbars / fused Pallas — see
:mod:`repro.core.backends`).  This is the public API the examples and
benchmarks use; ``TwinFleet`` scales it to N independent twins in one
device program.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.analogue import AnalogueSpec, program_mlp
from repro.core.backends import (AnalogueBackend, Backend, DigitalBackend,
                                 FusedPallasBackend, resolve_backend)
from repro.core.node import CDEVectorField, MLPVectorField, NeuralODE
from repro.core.ode import odeint

Pytree = Any


@dataclasses.dataclass(frozen=True)
class DigitalTwin:
    """Continuous-time digital twin of a physical asset."""
    field: Any                       # f(t, y, params)
    node: NeuralODE
    state_dim: int

    @property
    def backend(self) -> Backend:
        return resolve_backend(self.node.backend)

    def init(self, key: jax.Array) -> Pytree:
        return self.field.init(key)

    def with_backend(self, backend) -> "DigitalTwin":
        """Return the same twin executing on another substrate.

        ``backend``: a Backend instance or registry name ('digital',
        'analogue', 'fused_pallas').  The weights stay wherever the
        caller keeps them — ``simulate(params, ...)`` programs them onto
        the substrate at solve time.
        """
        backend = resolve_backend(backend)
        return dataclasses.replace(
            self, node=dataclasses.replace(self.node, backend=backend))

    def simulate(self, params: Pytree, y0: jax.Array, ts: jax.Array):
        return self.node.trajectory(params, y0, ts)

    def simulate_batch(self, params: Pytree, y0s: jax.Array, ts: jax.Array,
                       *, drive_family: Optional[Callable] = None,
                       drive_params: Optional[jax.Array] = None,
                       mesh=None):
        """Batched fleet rollout: (N, D) initial conditions -> (N, T+1, D),
        equal to stacking N single-trajectory solves but executed as one
        device program (vmap, or one Pallas grid for the fused backend).

        ``mesh``: optional ``jax.sharding.Mesh`` with a ``"twins"`` axis
        — shards the fleet dimension across devices (weights replicated,
        uneven N padded, padding dropped); ``None`` stays single-device.
        """
        return self.node.trajectory_batch(params, y0s, ts,
                                          drive_family=drive_family,
                                          drive_params=drive_params,
                                          mesh=mesh)

    def deploy_analogue(self, key: jax.Array, params: Pytree,
                        spec: AnalogueSpec,
                        read_key: Optional[jax.Array] = None) -> "DigitalTwin":
        """Deprecated: use ``twin.with_backend(AnalogueBackend(spec=spec,
        prog_key=key, read_key=read_key))`` and keep passing ``params``.

        Kept as a thin shim: programs the crossbars eagerly so the legacy
        ``simulate(None, y0, ts)`` call pattern still works.
        """
        warnings.warn(
            "DigitalTwin.deploy_analogue is deprecated; use "
            "twin.with_backend(AnalogueBackend(...)) instead",
            DeprecationWarning, stacklevel=2)
        progs = tuple(program_mlp(key, params, spec))
        return self.with_backend(
            AnalogueBackend(spec=spec, read_key=read_key, progs=progs))


# ---------------------------------------------------------------------------
# Fleets of twins — many assets, one device program
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TwinFleet:
    """N independent instances of one trained twin (one per physical
    asset), rolled out in a single device program.

    ``drive_family(t, theta) -> u`` is a parametric stimulus family;
    each fleet member i gets ``drive_params[i]`` (e.g. its own sensed
    amp/freq).  Autonomous fleets leave both None.

    Execution follows the underlying twin's backend: digital/analogue
    fleets vmap, the fused-Pallas fleet batch-tiles the kernel grid so
    all N trajectories run weights-stationary in one ``pallas_call``.
    """
    twin: DigitalTwin
    drive_family: Optional[Callable] = None

    @property
    def backend(self) -> Backend:
        return self.twin.backend

    def with_backend(self, backend) -> "TwinFleet":
        return dataclasses.replace(self, twin=self.twin.with_backend(backend))

    def simulate(self, params: Pytree, y0s: jax.Array, ts: jax.Array,
                 drive_params: Optional[jax.Array] = None) -> jax.Array:
        return self.rollout_batch(params, y0s, ts, drive_params)

    def rollout_batch(self, params: Pytree, y0s: jax.Array, ts: jax.Array,
                      drive_params: Optional[jax.Array] = None, *,
                      mesh=None) -> jax.Array:
        """Fleet rollout, optionally sharded over a multi-device mesh.

        ``mesh=None``: the whole fleet runs as one program on the current
        device (vmap / one Pallas grid).  ``mesh``: a ``jax.sharding.Mesh``
        with a ``"twins"`` axis — the fleet dimension of ``y0s`` and
        ``drive_params`` is split across devices with ``shard_map``
        (weights replicated, uneven N padded, padded rows dropped from
        the result), each device
        executing this fleet's backend on its slice.  Both paths return
        the same (N, T+1, D) trajectories; see
        :mod:`repro.launch.fleet_serving` for the serving pipeline on top.
        """
        if (drive_params is None) != (self.drive_family is None):
            raise ValueError(
                "drive_params and drive_family must be given together")
        return self.twin.simulate_batch(params, y0s, ts,
                                        drive_family=self.drive_family,
                                        drive_params=drive_params,
                                        mesh=mesh)

    def rollout_batch_resumed(self, params: Pytree, ys: jax.Array, *,
                              dt: float, num_steps: int, t0: float = 0.0,
                              start_steps=None,
                              drive_params: Optional[jax.Array] = None,
                              **kw) -> jax.Array:
        """Resume-from-state fleet rollout: advance each twin
        ``num_steps`` RK4 steps from its carried state ``ys[i]`` at its
        own global step ``start_steps[i]`` on the canonical uniform grid
        ``t = t0 + dt*k`` -> (N, num_steps+1, D).

        This is the streaming-serving primitive behind
        :class:`repro.launch.fleet_serving.StreamingFleetServer`: a twin
        served over ``[0, k)`` then ``[k, T)`` through a state store
        gets bit-identical trajectories (f32 substrates) to one served
        over ``[0, T)`` in a single request — see
        :meth:`repro.core.backends.BaseBackend.rollout_batch_resumed`
        for the determinism contract.  ``start_steps`` must be concrete
        host integers (they index the canonical float64 time grid).
        """
        if (drive_params is None) != (self.drive_family is None):
            raise ValueError(
                "drive_params and drive_family must be given together")
        node = self.twin.node
        backend = resolve_backend(node.backend)
        state = backend.program(node.field, params)
        return backend.rollout_batch_resumed(
            state, ys, dt=dt, num_steps=num_steps, t0=t0,
            start_steps=start_steps, drive_family=self.drive_family,
            drive_params=drive_params, **{**node._solver_kw(), **kw})


def simulate_batch(twin: DigitalTwin, params: Pytree, y0s: jax.Array,
                   ts: jax.Array, **kw) -> jax.Array:
    """Function-style alias for :meth:`DigitalTwin.simulate_batch`."""
    return twin.simulate_batch(params, y0s, ts, **kw)


def make_driven_twin(state_dim: int, drive: Callable, hidden: int = 14,
                     n_hidden_layers: int = 2, method: str = "rk4",
                     gradient: str = "adjoint",
                     steps_per_interval: int = 1,
                     backend: Optional[Backend] = None) -> DigitalTwin:
    """HP-memristor-style twin: dy/dt = MLP([u(t), y]).

    Default sizes (2 -> 14 -> 14 -> 1) are the paper's three crossbar
    arrays (2x14, 14x14, 14x1) for state_dim=1.
    """
    sizes = (1 + state_dim,) + (hidden,) * n_hidden_layers + (state_dim,)
    field = MLPVectorField(sizes=sizes, drive=drive)
    node = NeuralODE(field=field, method=method, gradient=gradient,
                     steps_per_interval=steps_per_interval, backend=backend)
    return DigitalTwin(field=field, node=node, state_dim=state_dim)


def make_autonomous_twin(state_dim: int, hidden: int = 64,
                         n_hidden_layers: int = 2, method: str = "rk4",
                         gradient: str = "adjoint",
                         steps_per_interval: int = 1,
                         backend: Optional[Backend] = None) -> DigitalTwin:
    """Lorenz96-style twin: dy/dt = MLP(y) (no external stimulation)."""
    sizes = (state_dim,) + (hidden,) * n_hidden_layers + (state_dim,)
    field = MLPVectorField(sizes=sizes, drive=None)
    node = NeuralODE(field=field, method=method, gradient=gradient,
                     steps_per_interval=steps_per_interval, backend=backend)
    return DigitalTwin(field=field, node=node, state_dim=state_dim)


def make_cde_twin(state_dim: int = 90, control_channels: int = 21,
                  hidden: int = 40, n_hidden_layers: int = 4,
                  control: Optional[Callable] = None, method: str = "rk4",
                  gradient: str = "adjoint", steps_per_interval: int = 1,
                  backend: Optional[Backend] = None) -> DigitalTwin:
    """Neural-CDE twin: dz/dt = tanh(MLP(z)) · dX/dt, the MLP
    D -> H -> ... -> H -> D·Du with ``n_hidden_layers`` hidden widths and
    its output read as a (D, Du) matrix (:class:`CDEVectorField`).

    ``control(t) -> (Du,)`` is the control's derivative dX/dt; a fleet
    passes ``TwinFleet(twin, drive_family=lambda t, theta: ...)`` so that
    each twin has its own.  The defaults are the Speech Commands widths of
    Kidger et al. (arXiv:2005.08926): hidden channels 90, hidden-hidden 40,
    4 hidden layers, 20 MFCC channels plus time.
    """
    sizes = ((state_dim,) + (hidden,) * n_hidden_layers
             + (state_dim * control_channels,))
    field = CDEVectorField(sizes=sizes, control_channels=control_channels,
                           drive=control)
    node = NeuralODE(field=field, method=method, gradient=gradient,
                     steps_per_interval=steps_per_interval, backend=backend)
    return DigitalTwin(field=field, node=node, state_dim=state_dim)


def reference_trajectory(f: Callable, y0: jax.Array, ts: jax.Array, *args,
                         steps_per_interval: int = 16) -> jax.Array:
    """High-accuracy ground-truth solve (dense RK4) for data generation."""
    return odeint(f, y0, ts, *args, method="rk4",
                  steps_per_interval=steps_per_interval)
